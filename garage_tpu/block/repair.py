"""Scrub / repair / rebalance workers — batch-first.

Equivalent of reference src/block/repair.rs (SURVEY.md §2.5):
  - ScrubWorker: full-datastore integrity pass every 25-35 days
    (randomized, repair.rs:24,244-254), resumable via a persisted iterator
    checkpoint (60 s cadence), Start/Pause/Resume/Cancel commands,
    tranquilizer-throttled, corruption counter.
  - RepairWorker: one-shot: re-enqueue every referenced hash to resync,
    then walk the disk and enqueue every found block (repair.rs:35-155).
  - RebalanceWorker: move blocks to their primary dir after a data-layout
    change (repair.rs:531-626).
  - BlockStoreIterator: resumable hash-ordered walk of the block store
    with fixed-point progress (repair.rs:634-764).

TPU-first difference (the north-star design, BASELINE.md): the reference
scrubs strictly one block at a time — read, blake2, next
(repair.rs:438-490).  Here the iterator feeds *batches* to the BlockCodec:
whole prefix dirs gathered up to `batch_blocks` blocks per dispatch, so a
TPU codec turns scrub from CPU-bound into IO-bound.
"""

from __future__ import annotations

import asyncio
import logging
import os
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from ..utils.background import Worker, WorkerState
from ..utils.crdt import now_msec
from ..utils.data import Hash
from ..utils.direct_io import READ_MODES, accounting
from ..utils.migrate import Migrated
from ..utils.persister import Persister
from ..utils.timeline import Timeline
from ..utils.tranquilizer import Tranquilizer
from .parity import CODEWORD_STATES

logger = logging.getLogger("garage_tpu.block.repair")

SCRUB_INTERVAL_MIN = 25 * 86400   # ref repair.rs:24 (randomized 25-35 days)
SCRUB_INTERVAL_MAX = 35 * 86400
DEFAULT_SCRUB_TRANQUILITY = 4     # ref repair.rs:27
CHECKPOINT_INTERVAL = 60.0        # ref repair.rs:460-464
REPAIR_BATCH = 1000               # ref repair.rs:92-101 (sqlite-safe batches)

# What a scrub pass's wall time is partitioned into, by consecutive
# stamps of the one coroutine that runs it (`ScrubWorker.work`): the
# segments of a finished pass sum to its `scrub pass` span exactly.
# `other` is the residue: between two `work()` calls (the runner, the
# governor's pause, a paused pass), event-loop lag, what no stamp covers.
SCRUB_SEGMENTS = (
    "read_wait", "decompress", "codec_wait", "heal", "coverage_refresh",
    "parity_write", "purge", "checkpoint", "tranquilize", "other",
)

# The scrub pass's I/O lane: the threads on which the read-ahead lists,
# reads and inflates a batch, so that none of it stands in the loop's
# default executor, where the worker's own hops go (`ScrubWorker._hop`).
# A batch is cut into this many slices, each ONE submission that reads
# its files in order.  Chosen on the chip (PERF.md §6, PR 36); not a knob.
SCRUB_IO_THREADS = 4
_SCRUB_IO: Optional[ThreadPoolExecutor] = None
_SCRUB_IO_LOCK = threading.Lock()

# What the lane's own time is partitioned into.  A slice (one submission
# of the lane, `_read_slice`) is stamped from its submission to its last
# file done: `queue` until a lane thread takes it, then `open`, `pread`
# and `copy` inside each read (utils/direct_io.py `ReadAccount`),
# `inflate` inside zstd, and `other`, the residue: `close`, the health
# notes, the busy seconds' lock, the loop over the files, what a
# `FaultyDisk` injects.  The stages of a slice sum to its wall exactly.
# `list` is the listing's one submission, until its result.
SCRUB_IO_STAGES = ("list", "queue", "open", "pread", "copy", "inflate",
                   "other")

# The road a slice's reads took through the disk seam: `native`, all its
# files inside native/directio.cpp with the interpreter's lock dropped
# once for them all (`open`, `pread` and `copy` are then stamped there:
# `copy` is the native memcpy, made without the lock), or `python`, file
# by file through `read_file_direct` (a `FaultyDisk`, or a host where
# the library cannot be built).  What the code can see decides, nothing
# else: there is no setting.
SCRUB_IO_ROADS = ("native", "python")

# spans of a manager with no codec observer (unit fakes) go nowhere
_NO_TIMELINE = Timeline(size=1)


def _scrub_io() -> ThreadPoolExecutor:
    """The lane's one process-wide executor (workers are constructed per
    node and per test; an executor each would leak threads)."""
    global _SCRUB_IO
    with _SCRUB_IO_LOCK:
        if _SCRUB_IO is None:
            from ..utils.cpuprof import register_thread
            _SCRUB_IO = ThreadPoolExecutor(
                max_workers=SCRUB_IO_THREADS,
                thread_name_prefix="scrub-io",
                initializer=lambda: register_thread("scrub-io"),
            )
        return _SCRUB_IO


def _timeline(mgr) -> Timeline:
    """The ring the scrub road is traced in: the codec observer's, the
    one the feeder and the transport write and `device_timeline` reads."""
    obs = getattr(getattr(mgr, "codec", None), "obs", None)
    return obs.timeline if obs is not None else _NO_TIMELINE


class _PassAccount:
    """The exact-sum account of one scrub pass: every `mark(segment)`
    puts the time since the last stamp to that segment."""

    def __init__(self, t0_ns: int, resumed: bool = False):
        self.t0 = self.last = t0_ns
        self.resumed = resumed
        self.ns = dict.fromkeys(SCRUB_SEGMENTS, 0)
        self.flushed = dict.fromkeys(SCRUB_SEGMENTS, 0)
        self.blocks = self.bytes = self.batches = 0
        # the pass's codewords by what it did with them, the rows that
        # needed parity, those of them encoded on the host (members of
        # two batches), the sidecars its purge removed
        self.codewords = dict.fromkeys(CODEWORD_STATES, 0)
        self.rows_lacking = self.rows_host = self.purged = 0

    def mark(self, segment: str) -> Tuple[int, int]:
        """→ (the last stamp, now): the interval now owned by `segment`."""
        now = time.monotonic_ns()
        prev, self.last = self.last, now
        self.ns[segment] += now - prev
        return prev, now


class _LaneAccount:
    """The I/O lane's account of one slice, or of a batch (its slices
    added up): nanoseconds by `SCRUB_IO_STAGES` on the ring's clock,
    the slices' walls (submission → last file done), the CPU their
    threads used (`time.thread_time_ns`), the files read and their
    bytes by the read's mode (`direct_io.READ_MODES`), and the slices by
    the road their reads took (`SCRUB_IO_ROADS`)."""

    def __init__(self):
        self.ns = dict.fromkeys(SCRUB_IO_STAGES, 0)
        self.wall_ns = self.cpu_ns = self.slices = 0
        self.files = dict.fromkeys(READ_MODES, 0)
        self.bytes = dict.fromkeys(READ_MODES, 0)
        self.roads = dict.fromkeys(SCRUB_IO_ROADS, 0)   # slices by road

    def add(self, other: "_LaneAccount") -> None:
        for stage, ns in other.ns.items():
            self.ns[stage] += ns
        for road, n in other.roads.items():
            self.roads[road] += n
        self.wall_ns += other.wall_ns
        self.cpu_ns += other.cpu_ns
        self.slices += other.slices
        for mode in READ_MODES:
            self.files[mode] += other.files[mode]
            self.bytes[mode] += other.bytes[mode]

    def args(self) -> dict:
        """The account as a ring event carries it."""
        out = {f"{stage}_ms": round(ns / 1e6, 3)
               for stage, ns in self.ns.items()}
        out["cpu_ms"] = round(self.cpu_ns / 1e6, 3)
        out.update(self.files)
        return out


class BlockStoreIterator:
    """Hash-ordered walk over every block file across all data dirs,
    resumable from a serialized position (ref repair.rs:634-764).

    Position = last fully-processed 2-level prefix (0..65536); progress is
    prefix/65536 — equivalent to the reference's fixed-point fraction."""

    def __init__(self, roots: List[str], position: int = 0):
        self.roots = roots
        self.position = position  # next 2-byte prefix to scan
        self._prefixes: Optional[List[int]] = None  # existing dirs, sorted

    def progress(self) -> float:
        return self.position / 65536.0

    def is_done(self) -> bool:
        return self.position >= 65536

    def _scan_prefixes(self) -> List[int]:
        """Enumerate existing 2-level prefix dirs (≤256 listdir calls per
        root instead of probing all 65536 combinations)."""
        pref = set()
        for root in self.roots:
            try:
                level1 = os.listdir(root)
            except FileNotFoundError:
                continue
            for a in level1:
                if len(a) != 2:
                    continue
                try:
                    ai = int(a, 16)
                    level2 = os.listdir(os.path.join(root, a))
                except (ValueError, OSError):
                    continue
                for b in level2:
                    if len(b) == 2:
                        try:
                            pref.add((ai << 8) | int(b, 16))
                        except ValueError:
                            pass
        return sorted(pref)

    def next_prefix(self) -> Optional[List[Tuple[Hash, str, bool]]]:
        """All blocks under the next existing prefix dir:
        [(hash, path, compressed)]; None when the walk is complete."""
        if self._prefixes is None:
            self._prefixes = self._scan_prefixes()
        import bisect

        i = bisect.bisect_left(self._prefixes, self.position)
        if i >= len(self._prefixes) or self.is_done():
            self.position = 65536
            return None
        p = self._prefixes[i]
        self.position = p + 1
        d1, d2 = f"{p >> 8:02x}", f"{p & 0xFF:02x}"
        seen = {}
        for root in self.roots:
            d = os.path.join(root, d1, d2)
            try:
                names = os.listdir(d)
            except FileNotFoundError:
                continue
            for name in names:
                base = name[:-4] if name.endswith(".zst") else name
                if len(base) != 64 or name.endswith((".tmp", ".corrupted")):
                    continue
                try:
                    h = Hash(bytes.fromhex(base))
                except ValueError:
                    continue
                # prefer the compressed copy, first root wins (primary first)
                if bytes(h) not in seen or name.endswith(".zst"):
                    seen[bytes(h)] = (h, os.path.join(d, name), name.endswith(".zst"))
        return sorted(seen.values(), key=lambda t: bytes(t[0]))


class ScrubWorkerState(Migrated):
    """Persisted scrub state (ref repair.rs:165-232)."""

    VERSION_MARKER = b"GT01scrub"

    def __init__(
        self,
        position: int = 0,
        running: bool = False,
        paused: bool = False,
        time_next_run: int = 0,
        tranquility: int = DEFAULT_SCRUB_TRANQUILITY,
        corruptions: int = 0,
        time_last_complete: int = 0,
        time_last_start: int = 0,
    ):
        self.position = position
        self.running = running
        self.paused = paused
        self.time_next_run = time_next_run
        self.tranquility = tranquility
        self.corruptions = corruptions
        self.time_last_complete = time_last_complete
        self.time_last_start = time_last_start

    def fields(self):
        return [
            self.position, self.running, self.paused, self.time_next_run,
            self.tranquility, self.corruptions, self.time_last_complete,
            self.time_last_start,
        ]

    @classmethod
    def from_fields(cls, b):
        return cls(*b)


def randomize_next_scrub() -> int:
    return now_msec() + random.randint(
        SCRUB_INTERVAL_MIN * 1000, SCRUB_INTERVAL_MAX * 1000
    )


class ScrubWorker(Worker):
    """Batch-first scrub: BlockStoreIterator prefixes → codec.batch_verify
    (one device dispatch per batch) → corrupted blocks moved aside +
    requeued for resync."""

    def __init__(self, manager, persister: Optional[Persister] = None):
        self.manager = manager
        self.persister = persister
        st = persister.load() if persister is not None else None
        self.state: ScrubWorkerState = st or ScrubWorkerState(
            time_next_run=randomize_next_scrub()
        )
        self.iterator: Optional[BlockStoreIterator] = None
        if self.state.running:
            self.iterator = BlockStoreIterator(
                self._roots(), self.state.position
            )
        self.tranquilizer = Tranquilizer()
        self.coverage_refreshed = 0  # blocks re-fed to the EC accumulator
        self._last_checkpoint = time.monotonic()
        self._cmd: asyncio.Queue = asyncio.Queue()
        self._wake = asyncio.Event()
        # read-ahead: next prefix's file contents load while the current
        # one verifies; checkpoints record the VERIFIED position, not the
        # iterator's (which runs one prefix ahead)
        self._ra_task: Optional[asyncio.Task] = None
        # set while work() stands in `await` of the read-ahead: a batch
        # read under it is submitted at once, so hinting it to the pool
        # would stage, send and hash it twice (`_read_ahead`)
        self._awaiting_read = False
        self._verified_pos = self.state.position
        # the running pass's view of the parity index: which codeword
        # each block is in, and the verified blocks kept between batches
        # until a row of k is whole (block/parity.py ScrubMembership)
        self._members = None
        # whether this process has run the pass from its first block: a
        # resumed one cannot tell a codeword that lost a member
        self._pass_whole = False
        self._prev_pass_start = 0.0  # resumed pass: purge nothing extra
        # the running pass's account (None between passes), and the
        # counters it is flushed into after every batch
        self._acct: Optional[_PassAccount] = None
        metrics = getattr(getattr(manager, "system", None), "metrics", None)
        self.m_segments = self.m_passes = None
        self.m_bytes = self.m_blocks = None
        self.m_read = self.m_inflate_s = self.m_inflate_bytes = None
        self.m_hop_wait = self.m_hints = None
        self.m_io_s = self.m_io_cpu = self.m_io_wall = None
        self.m_io_bytes = self.m_io_files = self.m_io_slices = None
        if metrics is not None:
            self.m_segments = metrics.counter(
                "scrub_pass_seconds_total",
                "Wall seconds of scrub passes by segment, partitioned by "
                "consecutive stamps: a finished pass's segments sum to "
                "its `scrub pass` timeline span; `other` is the residue")
            self.m_passes = metrics.counter(
                "scrub_passes_total", "Scrub passes run to their end")
            self.m_bytes = metrics.counter(
                "scrub_verified_bytes_total",
                "Block bytes the scrub handed to the codec (parity carry "
                "lanes, which are hashed again, not counted)")
            self.m_blocks = metrics.counter(
                "scrub_verified_blocks_total",
                "Blocks the scrub handed to the codec: the lanes behind "
                "scrub_verified_bytes_total")
            self.m_read = metrics.counter(
                "scrub_read_bytes_total",
                "Bytes of block files the scrub read from disk, by the "
                "form of the file (zst | plain): what the disk held of "
                "scrub_verified_bytes_total's content")
            self.m_io_s = metrics.counter(
                "scrub_io_seconds_total",
                "Seconds of the scrub's I/O lane by stage, stamped where "
                "the work happens: list (the listing's submission to its "
                "result), and for every slice of a batch queue | open | "
                "pread | copy | inflate | other, thread-seconds that sum "
                "to the slices' walls (submission to last file done)")
            self.m_io_cpu = metrics.counter(
                "scrub_io_cpu_seconds_total",
                "CPU seconds the I/O lane's threads used inside their "
                "slices (thread_time): beside the stages that run on the "
                "thread it says whether the lane computes or waits")
            self.m_io_wall = metrics.counter(
                "scrub_io_wall_seconds_total",
                "Wall seconds of the I/O lane's batches, listing "
                "included: each `read files` interval of the timeline, "
                "the lane's critical path for a batch")
            self.m_io_bytes = metrics.counter(
                "scrub_io_bytes_total",
                "Bytes of the block files the I/O lane read, by the "
                "read's mode: direct (O_DIRECT all through) | buffered "
                "(fell back at the open or mid-file)")
            self.m_io_files = metrics.counter(
                "scrub_io_files_total",
                "Block files the I/O lane read, by the read's mode: the "
                "files behind scrub_io_bytes_total")
            self.m_io_slices = metrics.counter(
                "scrub_io_slices_total",
                "Slices of the I/O lane's batches by the road their reads "
                "took: native (all the slice's files inside native code, "
                "the interpreter's lock dropped once for them) | python "
                "(file by file: a wrapped disk, or no library)")
            self.m_inflate_s = metrics.counter(
                "scrub_decompress_seconds_total",
                "Seconds inside the scrub's zstd decompressions, summed "
                "over the I/O lane's threads that ran them, counted once "
                "a block handed to the codec")
            self.m_inflate_bytes = metrics.counter(
                "scrub_decompress_bytes_total",
                "Bytes the scrub's decompressions took (dir=in: the "
                ".zst files) and gave (dir=out: their content)")
            self.m_hop_wait = metrics.counter(
                "scrub_hop_wait_seconds_total",
                "Seconds the scrub worker's off-loop steps stood in the "
                "executor's queue, submission to the thread starting "
                "them, by the pass segment that awaited them")
            self.m_hints = metrics.counter(
                "scrub_prefetch_hints_total",
                "Batches the read-ahead hinted to the device pool "
                "(hint=sent) or kept back because the worker was already "
                "waiting for them (hint=skipped)")

    def _roots(self) -> List[str]:
        return [d.path for d in self.manager.data_layout.data_dirs]

    def name(self) -> str:
        return "Block scrub worker"

    # --- operator commands (ref repair.rs Start/Pause/Resume/Cancel) ---

    def send_command(self, cmd: str) -> None:
        self._cmd.put_nowait(cmd)
        self._wake.set()

    def set_tranquility(self, t: int) -> None:
        t = int(t)
        if t < 0:
            raise ValueError("scrub-tranquility must be >= 0")
        self.state.tranquility = t
        self._checkpoint(force=True)

    def _apply_command(self, cmd: str) -> None:
        st = self.state
        if cmd == "start":
            if self.iterator is None:
                self.iterator = BlockStoreIterator(self._roots())
                st.running, st.paused, st.position, st.corruptions = True, False, 0, 0
                self._verified_pos = 0
                self._begin_pass()
                self._drop_read_ahead()
                self._members, self._pass_whole = None, True
                # purge grace is ONE pass: remember the previous start
                # before overwriting it (a sidecar skipped this pass —
                # its row held the corruption being repaired — must
                # survive until the NEXT pass refreshes it)
                self._prev_pass_start = st.time_last_start / 1000.0
                st.time_last_start = now_msec()
                # one full scrub pass == one device-pool clock tick: the
                # pool's LRU ages in scrub CYCLES, not wall time, so an
                # idle cluster never evicts its warm working set while
                # nothing else competes for pages (ops/device_pool.py)
                pool = getattr(self.manager.codec, "pool", None)
                if pool is not None:
                    pool.tick()
        elif cmd == "pause":
            st.paused = True
        elif cmd == "resume":
            st.paused = False
        elif cmd == "cancel":
            self.iterator = None
            st.running, st.paused, st.position = False, False, 0
            self._verified_pos = 0
            self._drop_read_ahead()
            self._members = None
            self._flush_account()   # a cancelled pass has no span
            self._acct = None
        self._checkpoint(force=True)
        if self._acct is not None:
            self._acct.mark("checkpoint")

    def _membership(self, store):
        if self._members is None:
            self._members = store.begin_pass(self._pass_whole)
        return self._members

    def _drop_read_ahead(self) -> None:
        if self._ra_task is not None:
            self._ra_task.cancel()
            self._ra_task = None

    def _checkpoint(self, force: bool = False) -> bool:
        """→ whether the state was written."""
        if self.persister is None:
            return False
        if force or time.monotonic() - self._last_checkpoint > CHECKPOINT_INTERVAL:
            # resume must re-verify anything not actually verified yet, so
            # the persisted position trails the (read-ahead) iterator
            self.state.position = self._verified_pos if self.iterator else 0
            self.persister.save(self.state)
            self._last_checkpoint = time.monotonic()
            return True
        return False

    # --- the pass's account and span tree (docs/OBSERVABILITY.md,
    #     "Device timeline"): track `scrub`, cat `scrub`, one event a
    #     batch or a pass, never one a block ---

    def _begin_pass(self, resumed: bool = False) -> None:
        t0 = time.monotonic_ns()
        # the profiler's clock against the ring's, once a pass: the
        # annotation starts just after the stamp it carries
        _timeline(self.manager).mark_clock(t0)
        self._acct = _PassAccount(t0, resumed)

    def _segment(self, segment: str, name: Optional[str] = None,
                 **args) -> None:
        """Close the interval since the last stamp as `segment`, with
        the timeline event `name` over the same two stamps.  Outside a
        pass (scrub_batch called on its own) there is no account."""
        if self._acct is None:
            return
        t0, t1 = self._acct.mark(segment)
        if name is not None:
            _timeline(self.manager).event(name, "scrub", t0, t1,
                                          cat="scrub", **args)

    def _flush_account(self) -> None:
        """What the account gained since the last flush, to the counters."""
        acct = self._acct
        if acct is None or self.m_segments is None:
            return
        for seg, ns in acct.ns.items():
            if ns > acct.flushed[seg]:
                self.m_segments.inc((ns - acct.flushed[seg]) / 1e9,
                                    segment=seg)
                acct.flushed[seg] = ns

    def _end_pass(self) -> None:
        acct = self._acct
        _timeline(self.manager).event(
            "scrub pass", "scrub", acct.t0, acct.last, cat="scrub",
            blocks=acct.blocks, bytes=acct.bytes, batches=acct.batches,
            rows=sum(acct.codewords.values()),
            rows_lacking=acct.rows_lacking, rows_host=acct.rows_host,
            purged=acct.purged,
            **acct.codewords,
            corruptions=self.state.corruptions, resumed=acct.resumed,
            **{f"{seg}_ms": round(ns / 1e6, 3)
               for seg, ns in acct.ns.items() if ns})
        self._flush_account()
        self._acct = None

    async def _hop(self, segment: str, fn, *args):
        """An off-loop step the worker awaits, in the loop's default
        executor (the read-ahead has threads of its own); how long it
        stood in that executor's queue is counted to `segment`."""
        t0 = time.monotonic_ns()
        began = []

        def run():
            began.append(time.monotonic_ns())
            return fn(*args)

        try:
            return await asyncio.to_thread(run)
        finally:
            if began and self.m_hop_wait is not None:
                self.m_hop_wait.inc((began[0] - t0) / 1e9, segment=segment)

    # --- the batch scrub step ---

    async def work(self) -> WorkerState:
        while not self._cmd.empty():
            self._apply_command(self._cmd.get_nowait())
        st = self.state
        status = self.status()
        status.tranquility = st.tranquility
        if self.iterator is None:
            # waiting for the next scheduled run
            if now_msec() >= st.time_next_run:
                self._apply_command("start")
                return WorkerState.BUSY
            return WorkerState.IDLE
        if st.paused:
            return WorkerState.IDLE
        if self._acct is None:
            # a pass resumed from the persisted state: its span and its
            # account cover what this process ran of it
            self._begin_pass(resumed=True)
        # since the last stamp: the runner between two work() calls
        self._acct.mark("other")
        self.tranquilizer.reset()
        task = self._ra_task or asyncio.ensure_future(self._read_ahead())
        # clear BEFORE awaiting: if the read fails, the next work() cycle
        # must retry a fresh read, not re-await the cached exception
        self._ra_task = None
        self._awaiting_read = True
        try:
            item = await task
        finally:
            self._awaiting_read = False
        self._segment("read_wait", "read wait")
        if item is None:
            # complete
            st.time_last_complete = now_msec()
            st.time_next_run = randomize_next_scrub()
            st.running = False
            # counted where the pass's end shows to whoever polls the
            # state, with what the account holds so far; the purge and
            # the checkpoint below still belong to the pass's span
            if self.m_passes is not None:
                self.m_passes.inc()
            self._flush_account()
            self.iterator = None
            store = self.manager.parity_store
            members, self._members = self._members, None
            if members is not None:
                # fewer than k free blocks left over: the next pass
                # retries.  A codeword this pass read fewer than k
                # members of is dissolved (index writes: off the loop)
                if members.unsettled:
                    await self._hop("parity_write", members.close)
                    self._segment("parity_write", "parity close",
                                  **members.counts)
                self._acct.codewords = members.counts
                self._acct.rows_lacking = members.asked
                self._acct.rows_host = members.host
            if store is not None:
                # write-time codewords are folded into the scrub's and
                # dissolved ones regrouped: drop sidecars refreshed by
                # NEITHER this pass nor the previous one, else orphans
                # accumulate forever (one-pass grace keeps coverage for
                # what waits: block/parity.py)
                self._acct.purged = await self._hop(
                    "purge", store.purge_stale, self._prev_pass_start)
                self._segment("purge", "purge stale", **store.last_purge)
            self._checkpoint(force=True)
            self._segment("checkpoint", "checkpoint")
            self._end_pass()
            logger.info("scrub complete, %d corruptions found", st.corruptions)
            return WorkerState.BUSY
        batch, reads, pos_after = item
        # prefetch the NEXT prefix while this one verifies: disk reads
        # overlap the codec dispatch (read→batch→device, SURVEY.md §3.4)
        self._ra_task = asyncio.ensure_future(self._read_ahead())
        status.progress = f"{self.iterator.progress() * 100:.2f}%"
        if batch:
            await self.scrub_batch(batch, reads)
        self._verified_pos = pos_after
        # the stamps before and after this stretch are scrub_batch's
        # last and the checkpoint's: what lies between is the checkpoint
        saved = self._checkpoint()
        self._segment("checkpoint", "checkpoint" if saved else None)
        state = await self.tranquilizer.tranquilize_worker(st.tranquility)
        self._segment("tranquilize",
                      "tranquilize" if st.tranquility > 0 else None)
        self._flush_account()
        return state

    async def _read_ahead(self):
        """Next batch, listed, read and inflated on the I/O lane's
        threads.  Returns (batch, reads, iterator_position_after), `reads`
        as `_read_batch` gives them, or None at end-of-store."""
        it = self.iterator
        if it is None:
            return None
        mgr = self.manager
        t0 = time.monotonic_ns()
        # gather prefix dirs until `codec.batch_blocks` blocks: one prefix
        # holds ~1 block below ~8M blocks per node, and the device wants
        # wide batches (the fused kernel starts at 128 lanes)
        batch = await asyncio.get_running_loop().run_in_executor(
            _scrub_io(), _list_batch, it,
            max(1, mgr.codec.params.batch_blocks))
        if batch is None:
            return None
        t_listed = time.monotonic_ns()
        reads, lane = await _read_batch(mgr, batch)
        lane.ns["list"] = t_listed - t0
        # the read-ahead itself, listing included, which overlaps the
        # worker's codec wait: on a track of its own, in no segment
        self._lane_done(batch, reads, lane, t0)
        # the lanes the real batch will have: (hash, what the codec takes)
        lanes = [(h, r) for (h, _p, _c), r in zip(batch, reads)
                 if isinstance(r, _Read)]
        got = [r for _h, r in lanes]
        # hint the device pool about the upcoming batch: the transport
        # stages it as background-class work WHILE the current batch
        # computes, so the next batch's H2D cost hides under compute and
        # its scrub becomes a pool hit.  Every lane the real batch will
        # have, so the hint has the batch's own geometry.  Only where it
        # can win: with the worker already awaiting this batch the real
        # submit is milliseconds away, the lookup would miss, and the
        # transport would stage, send and hash the batch twice
        feeder = mgr.feeder
        if feeder is not None and lanes:
            sent = not self._awaiting_read
            if sent:
                feeder.prefetch_scrub([r.data for r in got],
                                      [h for h, _r in lanes])
            if self.m_hints is not None:
                self.m_hints.inc(hint="sent" if sent else "skipped")
        return batch, reads, it.position

    def _lane_done(self, batch, reads, lane: "_LaneAccount",
                   t0: int) -> None:
        """A batch has come off the I/O lane: its `read files` event,
        from `t0` to now, carrying the lane's account, and the same
        numbers to the counters.  Once a batch, on the loop's thread; a
        read-ahead dropped before this counts nothing."""
        t1 = time.monotonic_ns()
        _timeline(self.manager).event(
            "read files", "scrub-io", t0, t1, cat="scrub",
            blocks=len(batch), bytes=sum(lane.bytes.values()),
            inflated=sum(r.inflated for r in reads if isinstance(r, _Read)),
            slices=lane.slices, slices_ms=round(lane.wall_ns / 1e6, 3),
            **lane.args())
        if self.m_io_s is None:
            return
        for stage, ns in lane.ns.items():
            if ns:
                self.m_io_s.inc(ns / 1e9, stage=stage)
        self.m_io_cpu.inc(lane.cpu_ns / 1e9)
        self.m_io_wall.inc((t1 - t0) / 1e9)
        for mode in READ_MODES:
            if lane.files[mode]:
                self.m_io_files.inc(lane.files[mode], mode=mode)
                self.m_io_bytes.inc(lane.bytes[mode], mode=mode)
        for road, n in lane.roads.items():
            if n:
                self.m_io_slices.inc(n, road=road)

    async def scrub_batch(self, batch: List[Tuple[Hash, str, bool]],
                          reads: Optional[list] = None) -> None:
        """Verify one batch through the codec; quarantine corrupt blocks.

        Every block is verified on its content by the codec (the device
        dispatch): a `.zst` copy is decompressed first (by the I/O lane,
        the thread that read it), where the reference validates its zstd
        frame checksum only (block.rs:66-78).

        `reads` is the read-ahead's (`_read_batch`); without it the
        batch goes through the same lane here.  A caller that read the
        files itself may hand their bytes: those are sorted and inflated
        on the worker's own path, the one case the segment `decompress`
        is stamped for."""
        mgr = self.manager
        plain_idx, plain_blocks, plain_hashes = [], [], []
        if reads is None:
            t0 = time.monotonic_ns()
            reads, lane = await _read_batch(mgr, batch)
            self._lane_done(batch, reads, lane, t0)
            self._segment("read_wait", "read wait")
        own = [i for i, r in enumerate(reads) if isinstance(r, bytes)]
        if own:
            reads = list(reads)
            made = await self._hop(
                "decompress", lambda: [
                    _handed_over(reads[i], batch[i][2]) for i in own])
            for i, r in zip(own, made):
                reads[i] = r
            self._segment("decompress", "decompress", blocks=len(own),
                          inflated=sum(r.inflated for r in made))
        lost = []           # (hash, path) to quarantine and heal
        inflate_ns = inflate_out = 0
        read_bytes = {"zst": 0, "plain": 0}
        for i, ((h, path, _c), r) in enumerate(zip(batch, reads)):
            if r is None:
                continue
            if r is _READ_ERROR:
                # unreadable on media: the copy is as lost as a content
                # mismatch — quarantine it and let the sidecar/resync
                # ladder re-materialize a clean one
                lost.append((h, path))
                continue
            read_bytes[r.form] += r.file_bytes
            inflate_ns += r.inflate_ns
            if r.inflated:
                inflate_out += len(r.data)
            plain_idx.append(i)
            plain_blocks.append(r.data)
            plain_hashes.append(h)
        if self.m_read is not None:
            for form, n in read_bytes.items():
                if n:
                    self.m_read.inc(n, form=form)
            if read_bytes["zst"]:
                self.m_inflate_s.inc(inflate_ns / 1e9)
                self.m_inflate_bytes.inc(read_bytes["zst"], dir="in")
                self.m_inflate_bytes.inc(inflate_out, dir="out")
        await self._heal(lost)
        store = mgr.parity_store
        k = mgr.codec.params.rs_data
        if lost and not plain_blocks and store is not None and k > 0:
            # nothing of this batch to verify: its blocks are their
            # codewords' members all the same (block/parity.py, rule 4)
            await self._hop("parity_write", self._membership(store).plan,
                            [], [], [h for h, _path in lost])
            self._segment("parity_write")
        if plain_blocks:
            # which codeword each block is in is the parity index's to
            # say (block/parity.py): the rows that need their parity (a
            # lost sidecar's members, free blocks k at a time, with what
            # earlier batches kept for them) go in front, and nothing
            # but the verdicts leaves the device for the rest
            members = plan = None
            all_b, all_h, want_parity = plain_blocks, plain_hashes, False
            if store is not None and k > 0:
                members = self._membership(store)
                plan = await self._hop(
                    "parity_write", members.plan, plain_hashes, plain_blocks,
                    [h for h, _path in lost])
                all_b, all_h, want_parity = (plan.blocks, plan.hashes,
                                             plan.want)
                self._segment("parity_write", "parity ask",
                              rows=len(all_h) // k, lacking=len(plan.want),
                              straddling=len(plan.straddlers))
            nbytes = sum(len(b) for b in plain_blocks)
            if self._acct is not None:
                self._acct.batches += 1
                self._acct.blocks += len(plain_blocks)
                self._acct.bytes += nbytes
            if self.m_bytes is not None:
                self.m_bytes.inc(nbytes)
                self.m_blocks.inc(len(plain_blocks))
            # span per fused dispatch: a slow batch (gated link, mid-pass
            # XLA compile, a CPU-side batch) shows up in the slow-op log
            # even on nodes with no trace_sink configured
            with mgr.system.tracer.span(
                "Scrub batch", blocks=len(all_b),
                bytes=sum(len(b) for b in all_b),
            ):
                # through the codec feeder when armed: scrub batches are
                # background-class submissions in the SAME queue as the
                # foreground verifies, so on a device-armed node they
                # enter the zero-copy transport deadline-ordered behind
                # live traffic instead of talking to the device behind
                # the feeder's back (ops/transport.py); a closed/absent
                # feeder keeps the pre-transport direct call
                if mgr.feeder is not None:
                    ok, parity = await mgr.feeder.scrub_async(
                        all_b, all_h, want_parity)
                else:
                    ok, parity = await self._hop(
                        "codec_wait", mgr.codec.scrub_encode_batch,
                        all_b, all_h, want_parity)
            self._segment("codec_wait", "codec wait", blocks=len(all_b),
                          bytes=nbytes)
            # the batch's own verdicts, in the batch's order
            good = (list(ok) if plan is None
                    else [ok[lane] for lane in plan.where])
            await self._heal([batch[plain_idx[j]][:2]
                              for j, g in enumerate(good) if not g])
            # Coverage refresh: verified blocks with NO live distributed
            # codeword (distribution failed at write time, coverage was
            # wrongly tombstoned, or the data predates EC) re-enter the
            # write-side accumulator — scrub makes erasure coverage
            # convergent, mirroring how it refreshes local sidecars.
            #
            # Because every refreshed block is stored on THIS node, the
            # accumulator's distinct-primary invariant flushes per block
            # and the refresh emits 1-member partial codewords.  That is
            # the intended SAFE shape for a single-node stream: the k−1
            # implicit zero shards are always-available pieces, so the
            # member survives the loss of up to m of its parity nodes —
            # full m-loss tolerance at m×(block size) overhead, paid only
            # for refreshed blocks (rewritten objects regroup at k).
            acc = mgr.ec_accumulator
            if acc is not None and acc.distributor is not None:
                from .block import DataBlock

                # NOT gated on acc.recently_added: that LRU remembers
                # the WRITE-time add, which is exactly the add whose
                # coverage may have been lost — locally_covered is
                # the authoritative duplicate guard, and a rare
                # double codeword (add raced an in-flight flush) is
                # benign extra parity, reclaimed by normal GC
                cand = [(h, b) for h, b, g in zip(plain_hashes,
                                                  plain_blocks, good)
                        if g and not mgr.is_parity_block(h)]

                def _uncovered():
                    # one off-loop hop for the whole batch: the per-hash
                    # index probes are synchronous DB iteration
                    d = acc.distributor
                    return [
                        (h, b) for h, b in cand
                        if d.holds_index_for(h) and not d.locally_covered(h)
                    ]

                refreshed = 0
                for h, b in await self._hop("coverage_refresh", _uncovered):
                    self.coverage_refreshed += 1
                    refreshed += 1
                    acc.add(h, DataBlock.plain(b))
                self._segment("coverage_refresh", "coverage refresh",
                              candidates=len(cand), refreshed=refreshed)
            if plan is not None:
                # persist RS sidecars for the rows whose members all
                # verified — this is what makes a later corruption
                # locally repairable with zero network (the BlockCodec
                # north star's decode-repair half): the front rows from
                # the parity that came back, a row whose members were
                # verified in two batches encoded where the write-time
                # codewords are; a thread hop a row
                written = touched = par_bytes = 0
                sound, straddling = members.filed(plan, ok)
                for row, cw, hashes, blocks in sound:
                    # trim to the row's own width: pad columns beyond the
                    # longest member are zero parity (GF-linear) and would
                    # bloat the sidecar to the batch-global maxlen
                    row_max = max(len(b) for b in blocks)
                    row_parity = np.asarray(parity[row])[:, :row_max]
                    if await self._hop(
                        "parity_write", store.put_codeword, hashes,
                        [len(b) for b in blocks], row_parity,
                    ):
                        written += 1
                        par_bytes += row_parity.nbytes
                    else:
                        touched += 1
                    members.wrote(cw)
                for cw, hashes, blocks in straddling:
                    if await self._hop("parity_write", store.put_straddler,
                                       hashes, blocks):
                        written += 1
                    else:
                        touched += 1
                    members.wrote(cw, host=True)
                if plan.rows or plan.straddlers:
                    self._segment("parity_write", "parity write",
                                  rows=len(plan.rows) + len(plan.straddlers),
                                  written=written, touched=touched,
                                  bytes=par_bytes, host=len(straddling))

    async def _heal(self, lost: List[Tuple[Hash, str]]) -> None:
        """Quarantine and heal a batch's lost copies as one stamped
        section; `how` says where the heals came from."""
        if not lost:
            return
        hows = [await self._quarantine(h, path) for h, path in lost]
        how = hows[0] if len(set(hows)) == 1 else "mixed"
        self._segment("heal", "quarantine+heal", blocks=len(lost), how=how,
                      local_sidecar=hows.count("local_sidecar"))

    async def _quarantine(self, h: Hash, path: str) -> str:
        """→ how the copy is healed: `local_sidecar` (rebuilt here, now)
        or `resync` (queued for a fetch from the replicas)."""
        self.state.corruptions += 1
        self.manager.corruptions += 1
        logger.error("scrub: corrupted block %s at %s", bytes(h).hex()[:16], path)
        # manager.quarantine_path: counted (block_quarantine_total), and
        # a failing rename deletes the bad copy instead of silently
        # leaving it servable (the old _move_aside swallowed OSError)
        self.manager.pool_invalidate(h, "quarantine")
        await self._hop("heal", self.manager.quarantine_path, path)
        # first line of defense: rebuild locally from the RS parity
        # sidecar — with every replica down this is the ONLY repair;
        # network resync stays as the fallback
        store = self.manager.parity_store
        if store is not None:
            data = await self._hop("heal", store.try_reconstruct, h)
            if data is not None:
                await self.manager.store_rebuilt(h, data)
                self.manager.blocks_reconstructed += 1
                self.manager.note_heal("local_sidecar")
                return "local_sidecar"
        if self.manager.resync is not None:
            self.manager.resync.put_to_resync(h, 0.0,
                                              source="scrub_corrupt")
        return "resync"

    async def wait_for_work(self) -> None:
        self._wake.clear()
        delay = max(1.0, (self.state.time_next_run - now_msec()) / 1000.0)
        try:
            await asyncio.wait_for(self._wake.wait(), timeout=min(delay, 10.0))
        except asyncio.TimeoutError:
            pass


class LayoutSweepMarker(Migrated):
    """Ring-assignment digest persisted AFTER a layout sweep completes: a
    node that crashed mid-sweep (or was down for the layout change
    entirely) finds a stale digest at startup and re-sweeps — without
    this, gained assignments would hold holes until the next unrelated
    ring change."""

    VERSION_MARKER = b"GT01lsweep"

    def __init__(self, digest: bytes = b""):
        self.digest = digest

    def fields(self):
        return [self.digest]

    @classmethod
    def from_fields(cls, body):
        return cls(bytes(body[0]))


class RepairWorker(Worker):
    """One-shot consistency repair (ref repair.rs:35-155): phase 1 enqueues
    every referenced hash to resync; phase 2 walks the disk and enqueues
    every found block (catches rc=0 leftovers).

    refs_only=True runs phase 1 alone — the shape used by the automatic
    layout-change sweep (spawned on every ring change): a ring change by
    itself fires no table hook, so a node that GAINED the assignment for
    an already-referenced block (rc>0, no 0→1 incref) would otherwise
    hold a hole until an operator ran `repair blocks`.  The reference
    leaves this to the operator; the sweep makes post-failure healing
    self-driven.  restart() rewinds a still-running sweep instead of
    stacking a second one (ring changes arrive in bursts as a layout
    propagates); on_done fires once when the sweep completes (the model
    layer persists the swept ring digest there)."""

    def __init__(self, manager, refs_only: bool = False, on_done=None):
        self.manager = manager
        self.refs_only = refs_only
        self.on_done = on_done
        self.phase = 1
        self.cursor: Optional[bytes] = b""
        self.iterator: Optional[BlockStoreIterator] = None
        self.finished = False

    def restart(self) -> None:
        self.phase = 1
        self.cursor = b""
        self.iterator = None

    def _done(self) -> WorkerState:
        self.finished = True
        if self.on_done is not None:
            try:
                self.on_done()
            except Exception:
                # e.g. marker persistence hitting disk-full: the sweep
                # itself succeeded, but the node will re-sweep at next
                # boot — say so instead of hiding the degradation
                logger.warning("repair worker on_done callback failed",
                               exc_info=True)
        return WorkerState.DONE

    def name(self) -> str:
        return "Block layout sweep" if self.refs_only else "Block repair worker"

    async def work(self) -> WorkerState:
        mgr = self.manager
        if self.phase == 1:
            # phase 1 is pure CPU (db iteration) and the worker runner
            # re-invokes BUSY workers back-to-back: yield the event loop
            # once per batch or a large rc table freezes RPC/S3 handling
            # for the whole scan — worst exactly when a layout change
            # just made the cluster fragile
            await asyncio.sleep(0)
            batch = 0
            while batch < REPAIR_BATCH:
                nxt = (
                    mgr.rc.tree.first()
                    if self.cursor == b""
                    else mgr.rc.get_gt(self.cursor)
                )
                if nxt is None:
                    if self.refs_only:
                        return self._done()
                    self.phase = 2
                    self.iterator = BlockStoreIterator(
                        [d.path for d in mgr.data_layout.data_dirs]
                    )
                    return WorkerState.BUSY
                key, _v = nxt
                mgr.resync.put_to_resync(
                    Hash(key), 0.0,
                    source="layout_sweep" if self.refs_only
                    else "repair_sweep")
                self.cursor = key
                batch += 1
            self.status().progress = "phase 1"
            # the backlog this sweep is generating: resync drains it, so
            # `worker list` shows sweep progress AND the induced queue
            self.status().queue_length = mgr.resync.queue_len()
            return WorkerState.BUSY
        batch = await asyncio.to_thread(self.iterator.next_prefix)
        if batch is None:
            return self._done()
        for h, _path, _c in batch:
            mgr.resync.put_to_resync(h, 0.0, source="repair_sweep")
        self.status().progress = f"phase 2: {self.iterator.progress() * 100:.1f}%"
        return WorkerState.BUSY


class RebalanceWorker(Worker):
    """One-shot: move blocks into their primary dir after a layout change,
    dropping secondary copies (ref repair.rs:531-626)."""

    def __init__(self, manager):
        self.manager = manager
        self.iterator = BlockStoreIterator(
            [d.path for d in manager.data_layout.data_dirs]
        )
        self.moved = 0

    def name(self) -> str:
        return "Block rebalance worker"

    async def work(self) -> WorkerState:
        mgr = self.manager
        batch = await asyncio.to_thread(self.iterator.next_prefix)
        if batch is None:
            logger.info("rebalance done, moved %d blocks", self.moved)
            return WorkerState.DONE
        for h, path, compressed in batch:
            primary = mgr.block_path(mgr.data_layout.primary_dir(h), h, compressed)
            if os.path.abspath(path) == os.path.abspath(primary):
                continue
            await asyncio.to_thread(_move_into_place, mgr, path, primary)
            self.moved += 1
        self.status().progress = f"{self.iterator.progress() * 100:.1f}%"
        return WorkerState.BUSY


# sentinel distinguishing "file unreadable, disk implicated" from a
# benign concurrent deletion (None): scrub_batch quarantines the former
_READ_ERROR = object()


def _try_read(mgr, path: str):
    """One scrub read through the manager's disk seam, judged
    (`_judged`): the bytes, None or ``_READ_ERROR``.  The I/O lane reads
    a slice's files in one call of the seam instead (`_read_slice`)."""
    from .health import read_or_error

    return _judged(mgr, path, read_or_error(mgr.disk.read_file_direct, path))


def _judged(mgr, path: str, got):
    """What the scrub makes of one read of the disk seam (DiskIo.
    read_file_direct / read_files_direct: O_DIRECT with buffered
    fallback — the buffered path is kernel-CPU-bound on 1-core hosts and
    scrubbing through the page cache evicts the GET path's working set,
    see utils/direct_io.py), `got` the bytes or the OSError.  Returns
    the bytes; None for a vanished file (deleted concurrently) or a
    transient resource error (EMFILE-class — skip this pass, the copy
    is fine); ``_READ_ERROR`` for a media error, after feeding the
    root's health accounting so a scrub churning through an EIO-ing
    disk shows up in disk_error_total and the root's breaker instead of
    staying silently 'ok'.

    A SUCCESSFUL read reports note_ok: the streak is *consecutive*
    errors, and on an archival node with no client GETs the scrub is
    the only reader — without the reset, isolated bad sectors spread
    over weeks of passes would accumulate into a streak and flip a
    fundamentally healthy root read-only."""
    from .health import is_media_error

    if isinstance(got, FileNotFoundError):
        return None
    if isinstance(got, OSError):
        if not is_media_error(got):
            logger.warning("scrub: transient read error on %s "
                           "(errno %s: %s)", path, got.errno, got)
            return None
        logger.error("scrub: read of %s failed (errno %s: %s)",
                     path, got.errno, got)
        mgr.health.note_error(mgr._root_of(path), "scrub", got)
        return _READ_ERROR
    mgr.health.note_ok(mgr._root_of(path), "scrub")
    return got


class _Read(NamedTuple):
    """A block file as the I/O lane hands it over: what the codec takes."""
    data: bytes       # the content; the file's own bytes where it is
    #                   plain or its frame does not decode
    file_bytes: int   # the file's length
    form: str         # the file's: "zst" | "plain"
    inflate_ns: int   # inside zstd
    inflated: bool    # `data` came out of zstd


def _list_batch(it: BlockStoreIterator, want: int):
    """Prefix dirs until they hold `want` blocks and, but for one dir
    that holds more by itself, never more: one lane past `want` is a
    geometry twice as wide, with programs of its own (a store that
    takes writes moves its batches' edges every pass, and one pass in a
    hundred built them inside the chip's window: PERF.md, PR 44).  The
    dir that would overfill is left for the next batch.  In one
    submission of the lane; None when the walk is complete."""
    batch = None
    while batch is None or len(batch) < want:
        before = it.position
        more = it.next_prefix()
        if more is None:
            break
        if batch and len(batch) + len(more) > want:
            it.position = before
            break
        batch = (batch or []) + more
    return batch


def _handed_over(raw: bytes, compressed: bool) -> _Read:
    """A file's bytes as the codec takes them.  A `.zst` file is
    decompressed so the codec verifies the CONTENT hash (a stronger
    check than the reference's zstd-checksum-only verify,
    block.rs:66-78) and the block joins a parity codeword: compressed
    blocks must be locally repairable too.  A frame that does not
    decode keeps its lane, as the file's own bytes: they fail the
    content hash like any corrupt block's, and the codewords after it
    keep their members (dropped, every later row of the pass would
    shift by one, lack its sidecar and be encoded and written anew)."""
    if not compressed:
        return _Read(raw, len(raw), "plain", 0, False)
    t0 = time.monotonic_ns()
    content = _try_decompress(raw)
    ns = time.monotonic_ns() - t0
    if content is None:
        return _Read(raw, len(raw), "zst", ns, False)
    return _Read(content, len(raw), "zst", ns, True)


def _read_slice(mgr, files, submitted_ns: int) -> Tuple[list, _LaneAccount]:
    """One submission of the lane: the slice's files read in ONE call of
    the disk seam (`DiskIo.read_files_direct`: inside native code that
    never takes the interpreter's lock between two files, where the
    library is there and nothing wraps the disk), then each judged as
    `_try_read` judges a single read and inflated on this thread, in
    order; and the slice's account (`SCRUB_IO_STAGES`), which is also
    its `read slice` span on the track `scrub-io` and says which road
    the reads took (`SCRUB_IO_ROADS`)."""
    timeline = _timeline(mgr)
    acct = _LaneAccount()
    acct.slices = 1
    out = []
    # the span's own stamps are the slice's: not in the ring until the
    # account it carries is whole
    with timeline.span("read slice", "scrub-io", cat="scrub",
                       record=False) as sp:
        cpu0 = time.thread_time_ns()
        with accounting() as rd:
            got = mgr.disk.read_files_direct([path for _h, path, _c in files])
            for (_h, path, compressed), raw in zip(files, got):
                raw = _judged(mgr, path, raw)
                if isinstance(raw, bytes):
                    raw = _handed_over(raw, compressed)
                    acct.ns["inflate"] += raw.inflate_ns
                out.append(raw)
        acct.cpu_ns = time.thread_time_ns() - cpu0
    road = "native" if rd.native_calls else "python"
    acct.roads[road] = 1
    acct.files, acct.bytes = rd.files, rd.bytes
    acct.wall_ns = sp.t1 - submitted_ns
    acct.ns.update(queue=sp.t0 - submitted_ns, open=rd.open_ns,
                   pread=rd.pread_ns, copy=rd.copy_ns)
    acct.ns["other"] = acct.wall_ns - sum(acct.ns.values())
    timeline.event("read slice", "scrub-io", sp.t0, sp.t1, cat="scrub",
                   files=len(files), bytes=sum(rd.bytes.values()),
                   wall_ms=round(acct.wall_ns / 1e6, 3), road=road,
                   **acct.args())
    return out, acct


async def _read_batch(mgr, batch) -> Tuple[list, _LaneAccount]:
    """→ (the batch's reads in its order, the lane's account of them):
    an item is a `_Read`, None for a vanished file or `_READ_ERROR`
    (`_try_read`).  The batch is cut into as many slices as the lane has
    threads; cancelled, the slices that run finish and are dropped."""
    loop = asyncio.get_running_loop()
    per = max(1, -(-len(batch) // SCRUB_IO_THREADS))
    parts = await asyncio.gather(*[
        loop.run_in_executor(_scrub_io(), _read_slice, mgr,
                             batch[lo:lo + per], time.monotonic_ns())
        for lo in range(0, len(batch), per)])
    lane = _LaneAccount()
    for _reads, acct in parts:
        lane.add(acct)
    return [r for reads, _acct in parts for r in reads], lane


def _try_decompress(raw: bytes) -> Optional[bytes]:
    from ..utils.zstd_compat import zstandard

    try:
        return zstandard.ZstdDecompressor().decompress(raw)
    except (zstandard.ZstdError, MemoryError):
        # MemoryError: a flipped frame-header bit can state a content
        # size no allocation serves (bit 6 of byte 4 does)
        return None


def _move_into_place(mgr, src: str, dst: str) -> None:
    """Rebalance move through the manager's disk seam so FaultyDisk can
    inject into it and a media error feeds the destination root's
    health accounting before surfacing to the worker error handler."""
    from .health import is_media_error

    try:
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        if os.path.exists(dst):
            mgr.disk.remove(src)
        else:
            mgr.disk.replace(src, dst)
    except OSError as e:
        if is_media_error(e):
            mgr.health.note_error(mgr._root_of(dst), "rebalance", e)
        raise

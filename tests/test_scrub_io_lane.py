"""The read-ahead is the scrub pass's I/O lane (ISSUE 36): a batch is
listed, read and inflated on threads of the lane's own and handed over
as what the codec takes; the worker's own hops go to the loop's default
executor, which no read enters, and say how long they stood in its
queue; the pool is hinted only when the worker is not already waiting
for the batch.  The lane accounts for itself (ISSUE 41): every slice's
stages, stamped where the work happens, sum to its wall, a batch's
counters grow by what its `read files` event says, and each read says
whether it was O_DIRECT.  A slice's files are read in one call of the
disk seam (ISSUE 42): inside native code where the library is there and
nothing wraps the disk, file by file otherwise, with one contract."""

import asyncio
import base64
import errno
import os
import resource
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from garage_tpu.block import DataBlock
from garage_tpu.block import repair
from garage_tpu.block.repair import (SCRUB_SEGMENTS, BlockStoreIterator,
                                     ScrubWorker, _Read, _READ_ERROR)
from garage_tpu.testing.faults import FaultyDisk
from garage_tpu.utils.data import Hash, blake2s_sum

N = 24 * 1024


def _without_library(monkeypatch):
    """A host where native/libdirectio.so cannot be built: the disk seam
    then reads a list file by file (`DiskIo.read_files_direct`)."""
    from garage_tpu.ops import native

    monkeypatch.setattr(native, "get_native_read_files", lambda: None)


@pytest.fixture
def no_library(monkeypatch):
    _without_library(monkeypatch)


def _content(i: int) -> bytes:
    """Odd: base64, which compresses; even: random, which does not."""
    raw = os.urandom(N)
    return base64.b64encode(raw)[:N] if i % 2 else raw


async def _store(tmp_path, blocks=16, parity=False):
    """→ (systems, the one manager, {hash bytes: content}): a store half
    `.zst` and half plain."""
    from garage_tpu.block.parity import ParityStore
    from garage_tpu.db import open_db
    from tests.test_block import make_block_cluster

    systems, (m,) = await make_block_cluster(tmp_path, n=1, mode="1")
    m.blocks_reconstructed = 0
    if parity:
        m.parity_store = ParityStore(m, open_db("memory"), m.codec)
    contents = {}
    for i in range(blocks):
        d = _content(i)
        contents[bytes(blake2s_sum(d))] = d
        await m.write_block(blake2s_sum(d), DataBlock.from_buffer(d, 1))
    return systems, m, contents


def _listing(m):
    """The whole store as the read-ahead lists it, in its order."""
    it = BlockStoreIterator([d.path for d in m.data_layout.data_dirs])
    return repair._list_batch(it, 1 << 30)


async def _one_pass(worker):
    worker.send_command("start")
    while (await worker.work()).name in ("BUSY", "THROTTLED"):
        pass


class _Feeder:
    """The two calls the scrub worker makes of a feeder."""

    def __init__(self, codec):
        self.codec = codec
        self.hints = []

    def prefetch_scrub(self, blocks, hashes):
        self.hints.append((list(blocks), list(hashes)))
        return sum(map(len, blocks))

    async def scrub_async(self, blocks, hashes, want_parity):
        return self.codec.scrub_encode_batch(blocks, hashes, want_parity)


# --- (a) what a batch comes back as ------------------------------------------


@pytest.mark.parametrize("threads", [1, 3, 17])
async def test_a_batch_comes_back_in_order_as_the_per_file_calls_give(
        tmp_path, monkeypatch, threads):
    from tests.test_table import shutdown

    monkeypatch.setattr(repair, "SCRUB_IO_THREADS", threads)
    systems, m, contents = await _store(tmp_path)
    batch = _listing(m)
    assert [bytes(h) for h, _p, _c in batch] == sorted(contents)
    zst = [i for i, (_h, _p, c) in enumerate(batch) if c]
    plain = [i for i, (_h, _p, c) in enumerate(batch) if not c]
    assert len(zst) == len(plain) == 8
    vanished, unreadable, undecodable = plain[1], plain[2], zst[3]
    os.remove(batch[vanished][1])
    with open(batch[undecodable][1], "r+b") as f:
        f.write(b"\x00\x00\x00\x00")            # no zstd magic
    fd = FaultyDisk(m.disk, path_prefix=batch[unreadable][1])
    fd.read_errno = errno.EIO
    m.disk = fd

    reads, lane = await repair._read_batch(m, batch)
    assert lane.slices == min(threads,
                              -(-len(batch) // -(-len(batch) // threads)))
    # the lane counts the reads that came back: neither the vanished
    # file nor the one the faulty disk refused
    assert sum(lane.files.values()) == len(batch) - 2
    assert len(reads) == len(batch)
    for i, ((h, path, compressed), r) in enumerate(zip(batch, reads)):
        raw = repair._try_read(m, path)         # the per-file call
        if i == vanished:
            assert r is None and raw is None
        elif i == unreadable:
            assert r is _READ_ERROR and raw is _READ_ERROR
        elif i == undecodable:
            # PR 35's lane: the file's own bytes, which fail the hash
            assert repair._try_decompress(raw) is None
            assert r == _Read(raw, len(raw), "zst", r.inflate_ns, False)
        elif compressed:
            assert r.data == contents[bytes(h)] == repair._try_decompress(raw)
            assert (r.file_bytes, r.form, r.inflated) == (len(raw), "zst", True)
            assert r.inflate_ns > 0
        else:
            assert r == _Read(raw, len(raw), "plain", 0, False)
            assert r.data == contents[bytes(h)]
    assert m.health.error_counts[("scrub", "EIO")] == 2     # lane + this loop
    await shutdown(systems)


async def test_scrub_batch_without_reads_goes_through_the_lane(
        tmp_path, monkeypatch):
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    names = []
    real = repair._read_slice
    monkeypatch.setattr(
        repair, "_read_slice", lambda *a: (
            names.append(threading.current_thread().name), real(*a))[1])
    w = ScrubWorker(m)
    await w.scrub_batch(_listing(m))
    assert names and all(n.startswith("scrub-io") for n in names)
    assert w.m_bytes.get() == sum(map(len, contents.values()))
    assert w.m_inflate_bytes.get(dir="out") == 8 * N
    assert w.state.corruptions == 0
    await shutdown(systems)


async def test_file_bytes_a_caller_read_itself_are_inflated_on_the_workers_path(
        tmp_path):
    """The one case the segment `decompress` is still stamped for."""
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    batch = _listing(m)
    raws = [repair._try_read(m, path) for _h, path, _c in batch]
    w = ScrubWorker(m)
    w._begin_pass()
    await w.scrub_batch(batch, reads=raws)
    w._flush_account()
    assert w.state.corruptions == 0
    assert w.m_bytes.get() == sum(map(len, contents.values()))
    assert w.m_segments.get(segment="decompress") > 0
    (ev,) = [e for e in m.codec.obs.timeline.snapshot()
             if e["name"] == "decompress"]
    assert ev["args"] == {"blocks": 16, "inflated": 8}
    await shutdown(systems)


def test_a_listed_batch_never_holds_more_than_asked_for(tmp_path):
    """A prefix dir that would take a batch past `want` is the next
    batch's first (a dir that holds more by itself is a batch); every
    block is listed once, in order, and the position after a batch
    does not pass the dir it left."""
    held = {0x0a00: 1, 0x0a01: 1, 0x0a02: 2, 0x0b00: 1, 0x0c00: 4, 0x0c01: 1}
    names = []
    for prefix, n in held.items():
        d = tmp_path / f"{prefix >> 8:02x}" / f"{prefix & 0xff:02x}"
        d.mkdir(parents=True)
        for i in range(n):
            name = f"{prefix:04x}" + f"{i:02x}" * 30
            (d / name).write_bytes(b"x")
            names.append(name)
    it = BlockStoreIterator([str(tmp_path)])
    batches, after = [], []
    while (batch := repair._list_batch(it, 3)) is not None:
        batches.append([bytes(h).hex() for h, _p, _c in batch])
        after.append(it.position)
    assert [len(b) for b in batches] == [2, 3, 4, 1]
    assert sum(batches, []) == names
    assert after == [0x0a02, 0x0b01, 0x0c01, 65536]


# --- (b) the worker's hops do not queue behind the reads ----------------------


async def test_a_hop_starts_at_once_while_a_batch_is_read(tmp_path,
                                                          monkeypatch):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path, blocks=40)
    readers = []

    class Counting(FaultyDisk):
        def read_file_direct(self, path):
            readers.append(threading.current_thread().name)
            return super().read_file_direct(path)

    fd = Counting(m.disk)
    fd.latency = 0.05                   # 40 reads: 0.5 s on the 4 threads
    m.disk = fd

    submitted = []

    class Recording(ThreadPoolExecutor):
        def submit(self, fn, *args, **kw):
            submitted.append(fn)
            return super().submit(fn, *args, **kw)

    default = Recording(max_workers=4, thread_name_prefix="default")
    asyncio.get_running_loop().set_default_executor(default)
    try:
        w = ScrubWorker(m)
        w.iterator = BlockStoreIterator(w._roots())
        task = asyncio.ensure_future(w._read_ahead())
        await asyncio.sleep(0.03)       # every slice is inside its first read
        assert not task.done()
        t0 = time.monotonic()
        assert await w._hop("parity_write", lambda: "ran") == "ran"
        assert time.monotonic() - t0 < 0.1
        assert not task.done()          # the batch was still being read
        batch, reads, _pos = await task
        assert w.m_hop_wait.get(segment="parity_write") < 0.05
        assert len(batch) == len(reads) == len(readers) == 40
        assert all(n.startswith("scrub-io") for n in readers)
        # the loop's default executor saw the hop and nothing else: no
        # read, no listing
        assert len(submitted) == 1
    finally:
        default.shutdown(wait=True)
    await shutdown(systems)


async def test_a_pass_counts_its_hops_by_segment(tmp_path):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path, parity=True)
    w = ScrubWorker(m)
    await _one_pass(w)
    waits = {dict(key)["segment"]: v for key, v in w.m_hop_wait._vals.items()}
    assert {"parity_write", "purge"} <= set(waits) <= set(SCRUB_SEGMENTS)
    assert all(v > 0 for v in waits.values())
    # a hop's wait lies inside the segment that awaited it
    for seg, v in waits.items():
        assert v <= w.m_segments.get(segment=seg)
    await shutdown(systems)


# --- (c) a dropped read-ahead --------------------------------------------------


async def test_a_read_ahead_dropped_mid_batch_leaves_no_task_and_counts_nothing(
        tmp_path):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path, blocks=40)
    m.feeder = _Feeder(m.codec)
    fd = FaultyDisk(m.disk)
    fd.latency = 0.03                   # 40 reads: 0.3 s on the 4 threads
    m.disk = fd
    w = ScrubWorker(m)
    w.iterator = BlockStoreIterator(w._roots())
    w._ra_task = task = asyncio.ensure_future(w._read_ahead())
    await asyncio.sleep(0.03)           # mid-batch: slices are running
    assert not task.done()
    w._drop_read_ahead()
    assert w._ra_task is None
    await asyncio.sleep(0.4)            # the slices that ran have ended
    assert task.cancelled()
    assert asyncio.all_tasks() == {asyncio.current_task()}
    for counter in (w.m_read, w.m_inflate_s, w.m_inflate_bytes, w.m_bytes,
                    w.m_hints, w.m_io_s, w.m_io_cpu, w.m_io_wall,
                    w.m_io_bytes, w.m_io_files):
        assert counter._vals == {}
    assert m.feeder.hints == []
    # the slices that ran left their spans; the batch left none
    names = {e["name"] for e in m.codec.obs.timeline.snapshot()}
    assert "read files" not in names and "read slice" in names
    await shutdown(systems)


# --- (d) the counters keep their meaning ----------------------------------------


async def test_two_passes_over_a_mixed_store_count_what_the_files_say(tmp_path):
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path, parity=True)
    on_disk = {"zst": 0, "plain": 0}
    inflated = 0
    for hb, d in contents.items():
        path, compressed = m.find_block(Hash(hb))
        on_disk["zst" if compressed else "plain"] += os.path.getsize(path)
        inflated += len(d) * compressed
    assert on_disk["zst"] and on_disk["plain"]
    w = ScrubWorker(m)
    for n in (1, 2):
        await _one_pass(w)
        assert w.state.corruptions == 0
        assert w.m_read.get(form="zst") == n * on_disk["zst"]
        assert w.m_read.get(form="plain") == n * on_disk["plain"]
        assert w.m_inflate_bytes.get(dir="in") == n * on_disk["zst"]
        assert w.m_inflate_bytes.get(dir="out") == n * inflated
        assert w.m_bytes.get() == n * sum(map(len, contents.values()))
        assert w.m_blocks.get() == n * len(contents)
        assert w.m_inflate_s.get() > 0
        # nothing of it is on the worker's path
        assert w.m_segments.get(segment="decompress") == 0
    evs = [e["args"] for e in m.codec.obs.timeline.snapshot()
           if e["name"] == "read files"]
    assert sum(a["inflated"] for a in evs) == 2 * 8
    assert sum(a["bytes"] for a in evs) == 2 * sum(on_disk.values())
    assert all(a["slices"] >= 1 for a in evs)
    assert abs(sum(a["inflate_ms"] for a in evs) / 1e3
               - w.m_inflate_s.get()) < 1e-3 * len(evs)
    await shutdown(systems)


# --- (e) the hint ----------------------------------------------------------------


async def test_a_read_ahead_the_worker_waits_for_sends_no_hint(tmp_path):
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    m.feeder = _Feeder(m.codec)
    w = ScrubWorker(m)
    w.send_command("start")
    await w.work()                      # reads the pass's one batch itself
    assert w.m_bytes.get() == sum(map(len, contents.values()))
    assert w.m_hints.get(hint="skipped") == 1
    assert w.m_hints.get(hint="sent") == 0
    assert m.feeder.hints == []
    assert not w._awaiting_read
    w._drop_read_ahead()            # the one that found the store's end
    await asyncio.sleep(0)
    await shutdown(systems)


async def test_a_read_ahead_that_ends_before_the_worker_waits_hints_every_lane(
        tmp_path):
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    m.feeder = _Feeder(m.codec)
    batch = _listing(m)
    with open(batch[1][1], "r+b") as f:     # one file with no sound frame
        f.write(b"\x00\x00\x00\x00")
    w = ScrubWorker(m)
    w._apply_command("start")
    w._ra_task = asyncio.ensure_future(w._read_ahead())
    await asyncio.wait([w._ra_task])    # the worker is busy elsewhere
    assert w.m_hints.get(hint="sent") == 1
    ((blocks, hashes),) = m.feeder.hints
    # every lane the real batch will have, the `.zst` ones as their
    # content: the hint has the batch's own geometry
    assert [bytes(h) for h in hashes] == sorted(contents)
    wrong = [i for i, (b, h) in enumerate(zip(blocks, hashes))
             if b != contents[bytes(h)]]
    assert wrong == [1] or not batch[1][2]
    await w.work()
    assert w.m_hints.get(hint="skipped") == 0
    assert w.m_blocks.get() == len(contents)
    w._drop_read_ahead()            # the one that found the store's end
    await asyncio.sleep(0)
    await shutdown(systems)


# --- (f) every file of a pass goes through the disk seam, once -------------------


@pytest.mark.parametrize("why", ["a faulty disk", "no library"])
async def test_on_the_per_file_road_every_file_goes_through_the_single_read(
        tmp_path, monkeypatch, why):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path)
    if why == "a faulty disk":
        m.disk = FaultyDisk(m.disk)     # the library is there: not asked
    else:
        _without_library(monkeypatch)
    calls = []
    real = m.disk.read_file_direct
    monkeypatch.setattr(
        m.disk, "read_file_direct",
        lambda path: (calls.append(path), real(path))[1])
    w = ScrubWorker(m)
    await _one_pass(w)
    assert sorted(calls) == sorted(p for _h, p, _c in _listing(m))
    assert w.state.corruptions == 0
    assert w.m_io_slices.get(road="python") == 4
    assert w.m_io_slices.get(road="native") == 0
    await shutdown(systems)


async def test_on_the_native_road_every_path_reaches_the_batch_read_once(
        tmp_path, monkeypatch):
    from garage_tpu.utils import direct_io
    from tests.test_table import shutdown

    assert direct_io.read_files_native([]) == ([], []), "no libdirectio.so"
    systems, m, _contents = await _store(tmp_path)
    lists, singles = [], []
    real = m.disk.read_files_direct
    monkeypatch.setattr(
        m.disk, "read_files_direct",
        lambda paths: (lists.append(list(paths)), real(paths))[1])
    monkeypatch.setattr(m.disk, "read_file_direct", singles.append)
    w = ScrubWorker(m)
    await _one_pass(w)
    assert len(lists) == 4 and singles == []        # a call a slice
    assert (sorted(p for paths in lists for p in paths)
            == sorted(p for _h, p, _c in _listing(m)))
    assert w.state.corruptions == 0
    assert w.m_io_slices.get(road="native") == 4
    assert w.m_io_slices.get(road="python") == 0
    roads = [e["args"]["road"] for e in m.codec.obs.timeline.snapshot()
             if e["name"] == "read slice"]
    assert roads == ["native"] * 4
    await shutdown(systems)


# --- (g) the lane's own account (ISSUE 41) --------------------------------------

ON_THREAD = ("open", "pread", "copy", "inflate", "other")
OTHER_ROAD = {"native": "python", "python": "native"}


def _slice_now(m, files):
    """One slice on the calling thread, submitted as it starts."""
    return repair._read_slice(m, files, time.monotonic_ns())


@pytest.mark.parametrize("road", ["native", "python"])
async def test_a_slices_stages_sum_to_its_wall_and_a_batch_counts_what_its_event_says(
        tmp_path, monkeypatch, road):
    from tests.test_table import shutdown

    if road == "python":
        _without_library(monkeypatch)
    systems, m, contents = await _store(tmp_path, parity=True)
    batch = _listing(m)
    t_sub = time.monotonic_ns()
    reads, acct = repair._read_slice(m, batch[:5], t_sub)
    assert [r.data for r in reads] == [contents[bytes(h)]
                                       for h, _p, _c in batch[:5]]
    # to the nanosecond: `other` is the residue of consecutive stamps
    assert sum(acct.ns.values()) == acct.wall_ns > 0
    assert acct.ns["list"] == 0 and acct.ns["queue"] >= 0
    assert all(acct.ns[s] > 0 for s in ("open", "pread", "other"))
    assert acct.ns["inflate"] == sum(r.inflate_ns for r in reads) > 0
    assert 0 < acct.cpu_ns
    assert sum(acct.files.values()) == 5 and acct.slices == 1
    assert sum(acct.bytes.values()) == sum(r.file_bytes for r in reads)
    (sl,) = [e for e in m.codec.obs.timeline.snapshot()
             if e["name"] == "read slice"]
    assert sl["args"]["files"] == 5 and sl["args"]["road"] == road
    assert acct.roads == {road: 1, OTHER_ROAD[road]: 0}
    assert sl["ts"] + sl["dur"] <= time.monotonic_ns() // 1000
    assert abs(sl["args"]["wall_ms"] * 1e6 - acct.wall_ns) < 1e3

    w = ScrubWorker(m)
    for _ in range(2):
        await _one_pass(w)
    evs = [e for e in m.codec.obs.timeline.snapshot()[1:]
           if e["cat"] == "scrub"]
    batches = [e for e in evs if e["name"] == "read files"]
    slices = [e for e in evs if e["name"] == "read slice"]
    assert len(batches) == 2 and len(slices) == sum(
        b["args"]["slices"] for b in batches) == 8
    near = lambda a, b, terms=1: abs(a - b) <= 0.0006 * terms   # noqa: E731
    for b in batches:
        a = b["args"]
        # the stages that belong to slices sum to the slices' walls,
        # as far as the ring's rounding to a microsecond a term lets see
        assert near(sum(a[f"{s}_ms"] for s in ("queue",) + ON_THREAD),
                    a["slices_ms"], 7)
        mine = [s for s in slices if b["ts"] <= s["ts"]
                and s["ts"] + s["dur"] <= b["ts"] + b["dur"] + 1]
        assert len(mine) == a["slices"]
        for s in mine:
            sa = s["args"]
            assert near(sum(sa[f"{st}_ms"] for st in ("queue",) + ON_THREAD),
                        sa["wall_ms"], 7)
            # the span is the slice less its queue
            assert abs(s["dur"] - (sa["wall_ms"] - sa["queue_ms"]) * 1e3) < 2.5
        for key in [f"{st}_ms" for st in ("queue",) + ON_THREAD] + [
                "cpu_ms", "direct", "buffered"]:
            assert near(sum(s["args"][key] for s in mine), a[key], 5), key
        assert a["direct"] + a["buffered"] == a["blocks"] == 16
        assert a["list_ms"] > 0
    # the counters' growth is the sum over the events
    for stage in repair.SCRUB_IO_STAGES:
        assert abs(w.m_io_s.get(stage=stage) * 1e3
                   - sum(b["args"][f"{stage}_ms"] for b in batches)) < 0.002
    assert abs(w.m_io_cpu.get() * 1e3
               - sum(b["args"]["cpu_ms"] for b in batches)) < 0.002
    assert abs(w.m_io_wall.get() * 1e6 - sum(b["dur"] for b in batches)) < 2.5
    for mode in ("direct", "buffered"):
        assert w.m_io_files.get(mode=mode) == sum(
            b["args"][mode] for b in batches)
    assert w.m_io_slices.get(road=road) == len(slices) == 8
    assert w.m_io_slices.get(road=OTHER_ROAD[road]) == 0
    assert (w.m_io_bytes.get(mode="direct") + w.m_io_bytes.get(mode="buffered")
            == sum(b["args"]["bytes"] for b in batches)
            == w.m_read.get(form="zst") + w.m_read.get(form="plain"))
    # the lane's critical path holds its longest slice, and the listing
    for b in batches:
        assert b["dur"] >= max(s["dur"] for s in slices
                               if b["ts"] <= s["ts"] <= b["ts"] + b["dur"])
    await shutdown(systems)


async def test_scrub_batch_on_its_own_counts_the_lane_too(tmp_path):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path)
    w = ScrubWorker(m)
    await w.scrub_batch(_listing(m))
    (ev,) = [e for e in m.codec.obs.timeline.snapshot()
             if e["name"] == "read files"]
    assert ev["args"]["list_ms"] == 0 and ev["args"]["slices"] == 4
    assert w.m_io_s.get(stage="list") == 0 < w.m_io_s.get(stage="pread")
    assert (w.m_io_files.get(mode="direct")
            + w.m_io_files.get(mode="buffered")) == 16
    assert abs(w.m_io_wall.get() * 1e6 - ev["dur"]) < 1.5
    await shutdown(systems)


async def test_a_slow_preadv_lands_in_pread(tmp_path, monkeypatch, no_library):
    from garage_tpu.utils import direct_io
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path)
    # an open that every filesystem takes, so the loop over preadv runs
    monkeypatch.setattr(direct_io, "_O_DIRECT", 0)
    real = os.preadv

    def slow(fd, bufs, off):
        time.sleep(0.05)
        return real(fd, bufs, off)

    monkeypatch.setattr(direct_io.os, "preadv", slow)
    _reads, acct = _slice_now(m, _listing(m)[:4])
    assert acct.ns["pread"] >= 4 * 0.05e9
    assert acct.ns["copy"] + acct.ns["other"] + acct.ns["open"] < 0.1e9
    assert sum(acct.ns.values()) == acct.wall_ns
    # a sleeping thread uses no CPU: the share that says "it waits"
    assert acct.cpu_ns < 0.5 * acct.ns["pread"]
    assert acct.files == {"direct": 0, "buffered": 4}
    await shutdown(systems)


async def test_a_lane_whose_threads_are_held_lands_in_queue(tmp_path):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path)
    release = threading.Event()
    held = [repair._scrub_io().submit(release.wait, 10)
            for _ in range(repair.SCRUB_IO_THREADS)]
    task = asyncio.ensure_future(repair._read_batch(m, _listing(m)))
    await asyncio.sleep(0.15)
    assert not task.done()
    release.set()
    _reads, lane = await task
    assert all(f.result(timeout=10) for f in held)
    assert lane.slices == 4 and lane.ns["queue"] >= 4 * 0.14e9
    assert sum(lane.ns[s] for s in ON_THREAD) < lane.ns["queue"]
    assert sum(lane.ns.values()) == lane.wall_ns
    await shutdown(systems)


@pytest.mark.parametrize("fault, mode", [
    ("the open refuses O_DIRECT", "buffered"),
    ("a chunk fails mid-file", "buffered"),
    ("none", "direct"),
])
async def test_a_read_says_whether_it_was_o_direct(tmp_path, monkeypatch,
                                                   no_library, fault, mode):
    """Whatever the test directory's filesystem makes of O_DIRECT: the
    opens are steered here, on the per-file road."""
    from garage_tpu.utils import direct_io
    from tests.test_table import shutdown

    if not direct_io._O_DIRECT:
        pytest.skip("no O_DIRECT on this platform")
    systems, m, contents = await _store(tmp_path)
    real_open, real_preadv = os.open, os.preadv
    asked = []

    def open_(path, flags, *a, **kw):
        if flags & os.O_DIRECT:
            asked.append(path)
            if fault == "the open refuses O_DIRECT":
                raise OSError(errno.EINVAL, "no O_DIRECT here", path)
        return real_open(path, flags & ~os.O_DIRECT, *a, **kw)

    def preadv(fd, bufs, off):
        if fault == "a chunk fails mid-file":
            raise OSError(errno.EINVAL, "unaligned")
        return real_preadv(fd, bufs, off)

    monkeypatch.setattr(direct_io.os, "open", open_)
    monkeypatch.setattr(direct_io.os, "preadv", preadv)
    batch = _listing(m)[:6]
    reads, acct = _slice_now(m, batch)
    monkeypatch.undo()
    assert sorted(asked) == sorted(p for _h, p, _c in batch)
    assert [r.data for r in reads] == [contents[bytes(h)]
                                       for h, _p, _c in batch]
    other = "buffered" if mode == "direct" else "direct"
    assert acct.files == {mode: 6, other: 0}
    assert acct.bytes[mode] == sum(r.file_bytes for r in reads)
    assert acct.bytes[other] == 0
    # a read that never reached the aligned buffer has no copy out of it
    assert (acct.ns["copy"] == 0) == (fault == "the open refuses O_DIRECT")
    assert acct.ns["pread"] > 0
    await shutdown(systems)


async def test_a_faulty_disk_still_wraps_the_read_unchanged(tmp_path):
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    batch = _listing(m)[:4]
    fd = FaultyDisk(m.disk)
    fd.latency = 0.04
    m.disk = fd
    reads, acct = _slice_now(m, batch)
    assert acct.roads == {"native": 0, "python": 1}
    assert [r.data for r in reads] == [contents[bytes(h)]
                                       for h, _p, _c in batch]
    # what the wrapper injects is the slice's residue, not the read's
    assert acct.ns["other"] >= 4 * 0.04e9 > acct.ns["pread"]
    assert sum(acct.files.values()) == 4
    assert sum(acct.ns.values()) == acct.wall_ns
    fd.latency = 0.0
    fd.path_prefix = batch[2][1]
    fd.read_errno = errno.EIO
    reads, acct = _slice_now(m, batch)
    assert reads[2] is _READ_ERROR and fd.injected["read"] == 1
    assert sum(acct.files.values()) == 3
    assert sum(acct.bytes.values()) == sum(
        r.file_bytes for r in reads if isinstance(r, _Read))
    fd.clear()
    fd.path_prefix = None
    fd.bitrot_prob = 1.0
    reads, acct = _slice_now(m, batch[:1])
    assert fd.injected["bitrot"] == 1 and sum(acct.files.values()) == 1
    assert reads[0].data != contents[bytes(batch[0][0])]
    await shutdown(systems)


# --- (i) the two roads through the disk seam (ISSUE 42) ---------------------------


def _fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def _same(a, b) -> bool:
    """Two reads of one file, but for the time zstd took."""
    if isinstance(a, _Read) and isinstance(b, _Read):
        return a._replace(inflate_ns=0) == b._replace(inflate_ns=0)
    return a is b


def _both_roads(monkeypatch, m, files):
    """→ (reads, account) of one slice on the native road, and of the
    same slice with the library gone."""
    got = _slice_now(m, files)
    with monkeypatch.context() as mp:
        _without_library(mp)
        per_file = _slice_now(m, files)
    assert got[1].roads == {"native": 1, "python": 0}
    assert per_file[1].roads == {"native": 0, "python": 1}
    return got, per_file


async def test_the_native_road_reads_what_the_per_file_road_reads(
        tmp_path, monkeypatch):
    from garage_tpu.utils import direct_io
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    # every file of the store is over a chunk, the last chunk short
    monkeypatch.setattr(direct_io, "_CHUNK", 8192)
    files = _listing(m)
    assert any(c for _h, _p, c in files) and not all(c for _h, _p, c in files)
    root = os.path.dirname(files[0][1])
    extra = {"empty": b"", "one": b"x", "unaligned": os.urandom(4097),
             "page": os.urandom(4096)}
    for name, data in extra.items():
        with open(os.path.join(root, name), "wb") as f:
            f.write(data)
        files.append((Hash(blake2s_sum(data)), os.path.join(root, name),
                      False))
    os.remove(files[2][1])                          # one vanished
    before = _fds()
    (reads, acct), (reads_pf, acct_pf) = _both_roads(
        monkeypatch, m, files)
    assert _fds() == before
    assert len(reads) == len(files)
    assert all(_same(a, b) for a, b in zip(reads, reads_pf))    # same order
    assert reads[2] is None
    for (h, _p, compressed), r in zip(files[:16], reads):
        if r is not None:
            assert r.data == contents[bytes(h)]
            assert r.inflated == compressed
    assert [r.data for r in reads[16:]] == list(extra.values())
    assert acct.files == acct_pf.files and acct.bytes == acct_pf.bytes
    assert sum(acct.files.values()) == len(files) - 1
    assert sum(acct.bytes.values()) == sum(r.file_bytes for r in reads if r)
    for a in (acct, acct_pf):
        assert sum(a.ns.values()) == a.wall_ns
        assert all(a.ns[s] > 0 for s in ("open", "pread", "other"))
    await shutdown(systems)


@pytest.mark.parametrize("road", ["native", "python"])
async def test_a_slice_with_errors_in_it_judges_each_and_leaves_no_fd_open(
        tmp_path, monkeypatch, road):
    from tests.test_table import shutdown

    if road == "python":
        _without_library(monkeypatch)
    systems, m, contents = await _store(tmp_path)
    files = _listing(m)[:8]
    vanished, unreadable = files[1][1], files[5][1]
    os.remove(vanished)
    os.remove(unreadable)
    os.mkdir(unreadable)        # a read of it fails with EISDIR: the media's
    root = m._root_of(unreadable)
    for _ in range(3):
        m.health.note_error(root, "scrub", OSError(errno.EIO, "io"))
    streak = m.health._streak[m.health._norm(root)]
    before = _fds()
    reads, acct = _slice_now(m, files)
    assert _fds() == before
    assert acct.roads[road] == 1
    assert reads[1] is None and reads[5] is _READ_ERROR
    assert m.health.error_counts[("scrub", "EISDIR")] == 1
    # in order: the streak grew at the fifth file and the three sound
    # reads after it walked it back
    assert streak == 3 and m.health._streak[m.health._norm(root)] == 0
    assert [r.data for r in reads if isinstance(r, _Read)] == [
        contents[bytes(h)] for h, p, _c in files
        if p not in (vanished, unreadable)]
    assert sum(acct.files.values()) == 6
    assert sum(acct.ns.values()) == acct.wall_ns

    # no descriptor left for the process: every open is refused, which
    # blames the process and not the disk, so every copy is skipped
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    highest = max(int(fd) for fd in os.listdir("/proc/self/fd"))
    errors = dict(m.health.error_counts)
    held = []
    resource.setrlimit(resource.RLIMIT_NOFILE, (highest + 1, hard))
    try:
        with pytest.raises(OSError) as full:
            while len(held) <= highest:     # the free numbers below the limit
                held.append(os.open(os.devnull, os.O_RDONLY))
        reads, acct = _slice_now(m, files[:4])
    finally:
        resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))
        for fd in held:
            os.close(fd)
    assert full.value.errno == errno.EMFILE
    assert reads == [None] * 4 and sum(acct.files.values()) == 0
    assert m.health.error_counts == errors
    assert m.health.state(root) == "ok"
    assert _fds() == before
    await shutdown(systems)


def test_an_eio_is_a_read_error_with_the_roots_health_noted(tmp_path):
    from garage_tpu.block.health import DiskHealthMonitor, DiskIo

    class Mgr:
        disk = DiskIo()
        health = DiskHealthMonitor([str(tmp_path)], watermark=0)

        def _root_of(self, path):
            return str(tmp_path)

    m = Mgr()
    path = str(tmp_path / "block")
    assert repair._judged(m, path, OSError(errno.EIO, "io", path)) is _READ_ERROR
    assert m.health.error_counts == {("scrub", "EIO"): 1}
    assert repair._judged(m, path, OSError(errno.EMFILE, "fds", path)) is None
    assert repair._judged(m, path, FileNotFoundError(2, "gone", path)) is None
    assert m.health.error_counts == {("scrub", "EIO"): 1}
    assert m.health._streak[str(tmp_path)] == 1
    assert repair._judged(m, path, b"sound") == b"sound"
    assert m.health._streak[str(tmp_path)] == 0


@pytest.mark.parametrize("refusal, copied", [
    # O_DIRECTORY stands in for a filesystem's EINVAL: the open of a
    # regular file with it fails, and is made again without the flag
    ("at the open", False),
    # a descriptor that cannot be read from: every chunk fails, and the
    # remainder (all of it) goes through a plain descriptor
    ("mid-file", True),
])
async def test_a_filesystem_that_refuses_o_direct_reads_buffered_on_both_roads(
        tmp_path, monkeypatch, refusal, copied):
    from garage_tpu.utils import direct_io
    from tests.test_table import shutdown

    systems, m, contents = await _store(tmp_path)
    monkeypatch.setattr(
        direct_io, "_O_DIRECT",
        os.O_DIRECTORY if refusal == "at the open" else os.O_WRONLY)
    files = _listing(m)[:6]
    before = _fds()
    for reads, acct in _both_roads(monkeypatch, m, files):
        assert [r.data for r in reads] == [contents[bytes(h)]
                                           for h, _p, _c in files]
        assert acct.files == {"direct": 0, "buffered": 6}
        assert acct.bytes == {"direct": 0, "buffered": sum(
            r.file_bytes for r in reads)}
        # a read that never reached the aligned buffer has no copy out of it
        assert (acct.ns["copy"] > 0) == copied
        assert sum(acct.ns.values()) == acct.wall_ns
    assert _fds() == before
    await shutdown(systems)


async def test_a_pass_under_a_faulty_disk_counts_the_python_road(tmp_path):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path)
    fd = FaultyDisk(m.disk)
    fd.bitrot_prob = 1.0                # every read of the pass is injected
    m.disk = fd
    w = ScrubWorker(m)
    w._begin_pass()
    await w.scrub_batch(_listing(m))
    assert fd.injected["bitrot"] == 16 == w.state.corruptions
    assert w.m_io_slices.get(road="python") == 4
    assert w.m_io_slices.get(road="native") == 0
    await shutdown(systems)


# --- (h) the lane's metric files, through the benchmark's own reader ---------------

IO_S = 'scrub_io_seconds_total{stage="%s"}'
IO_B = 'scrub_io_bytes_total{mode="%s"}'
IO_F = 'scrub_io_files_total{mode="%s"}'
IO_R = 'scrub_io_slices_total{road="%s"}'
LANE_BEFORE = {
    IO_S % "list": 1.0, IO_S % "queue": 2.0, IO_S % "open": 1.0,
    IO_S % "pread": 10.0, IO_S % "copy": 1.0, IO_S % "other": 1.0,
    "scrub_io_cpu_seconds_total": 5.0, "scrub_io_wall_seconds_total": 4.0,
    IO_B % "buffered": 2.0**30, IO_F % "buffered": 1024.0,
    IO_R % "python": 4.0,
    "scrub_verified_bytes_total": 2.0**30,
}
LANE_AFTER = {
    IO_S % "list": 1.25, IO_S % "queue": 2.5, IO_S % "open": 1.5,
    IO_S % "pread": 14.0, IO_S % "copy": 2.0, IO_S % "inflate": 2.0,
    IO_S % "other": 1.5,
    "scrub_io_cpu_seconds_total": 9.0, "scrub_io_wall_seconds_total": 7.0,
    IO_B % "buffered": 2 * 2.0**30, IO_B % "direct": 3 * 2.0**30,
    IO_F % "buffered": 2048.0, IO_F % "direct": 3072.0,
    IO_R % "python": 8.0, IO_R % "native": 12.0,
    "scrub_verified_bytes_total": 5 * 2.0**30,
}


@pytest.mark.parametrize("metric, value", [
    ("scrub_io_lane_ms_per_gib", 3000.0 / 4),           # 3 s over 4 GiB
    ("scrub_io_queue_ms_per_gib", 500.0 / 4),
    ("scrub_io_pread_mib_s.scrub", 4 * 1024 / 4.0),     # 4 GiB in 4 s
    ("scrub_io_open_us.scrub", 0.5e6 / 4096),           # 0.5 s, 4,096 files
    ("scrub_io_cpu_share.scrub", 100 * 4.0 / 8.0),      # 4 s of the five's 8
    ("scrub_io_direct_share.scrub", 75.0),              # 3 GiB of 4
    ("scrub_io_native_share.scrub", 75.0),              # 12 slices of 16
])
def test_each_lane_metric_file_reads_its_value_and_nothing_without_the_families(
        metric, value):
    from benchmarks import harness

    cell = harness.Cell("rep3-1m.scrub")
    assert metric in {m["name"] for m in cell.per_layer()}

    def window(before, after):
        return {"before": {"metrics": before, "codec_info": {},
                           "mono_us": 0},
                "after": {"metrics": after, "codec_info": {},
                          "mono_us": 10_000_000},
                "timeline": []}

    got = cell.read_per_layer(window(LANE_BEFORE, LANE_AFTER))
    assert got[metric]["value"] == pytest.approx(value)
    unit = {m["name"]: m["unit"] for m in cell.per_layer()}[metric]
    assert got[metric]["unit"] == unit
    # a program without the lane's families (the parent): left out, not 0
    old = {"scrub_verified_bytes_total": 2.0**30}
    assert metric not in cell.read_per_layer(
        window(old, {"scrub_verified_bytes_total": 5 * 2.0**30}))
    # the families there and nothing read in the window: left out too
    assert metric not in cell.read_per_layer(window(LANE_AFTER, LANE_AFTER))

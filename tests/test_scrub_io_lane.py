"""The read-ahead is the scrub pass's I/O lane (ISSUE 36): a batch is
listed, read and inflated on threads of the lane's own and handed over
as what the codec takes; the worker's own hops go to the loop's default
executor, which no read enters, and say how long they stood in its
queue; the pool is hinted only when the worker is not already waiting
for the batch."""

import asyncio
import base64
import errno
import os
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

    reads, slices = await repair._read_batch(m, batch)
    assert slices == min(threads, -(-len(batch) // -(-len(batch) // threads)))
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


# --- (b) the worker's hops do not queue behind the reads ----------------------


async def test_a_hop_starts_at_once_while_a_batch_is_read(tmp_path,
                                                          monkeypatch):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path, blocks=40)
    fd = FaultyDisk(m.disk)
    fd.latency = 0.05                   # 40 reads: 0.5 s on the 4 threads
    m.disk = fd
    readers = []
    real = repair._try_read

    def counted(mgr, path):
        readers.append(threading.current_thread().name)
        return real(mgr, path)

    monkeypatch.setattr(repair, "_try_read", counted)

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
                    w.m_hints):
        assert counter._vals == {}
    assert m.feeder.hints == []
    assert [e for e in m.codec.obs.timeline.snapshot()
            if e["name"] == "read files"] == []
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


# --- (f) what the benchmark's annotation relies on -----------------------------


async def test_the_lane_calls_try_read_through_the_modules_global_name(
        tmp_path, monkeypatch):
    from tests.test_table import shutdown

    systems, m, _contents = await _store(tmp_path)
    calls = []
    real = repair._try_read
    monkeypatch.setattr(
        repair, "_try_read",
        lambda mgr, path: (calls.append(path), real(mgr, path))[1])
    w = ScrubWorker(m)
    await _one_pass(w)
    assert sorted(calls) == sorted(p for _h, p, _c in _listing(m))
    assert w.state.corruptions == 0
    await shutdown(systems)

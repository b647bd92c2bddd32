"""DevicePool — device-resident block pages under the transport (ISSUE 18).

Covers the acceptance contract: hit/miss byte attribution is EXACT
(pool_hit + pool_miss == bytes scrubbed; transport_staged_bytes_total
flat on a warm pass), ragged-tail pages read back bit-identical,
scrub-cycle LRU evicts in cycle order, strict synchronous invalidation
(a post-invalidate read is a miss), the prefetch path staging ahead of
need with its overlap visible in the device timeline, pool-disabled
byte-identical legacy behavior, promlint + metricsdoc over the new
pool_* families — plus the satellite pieces: the O(1) incremental
BLAKE2 hash state's bit-identity against the one-shot digests, and the
feeder's gate-refresh short-circuit for fully-resident background
batches.
"""

import hashlib
import os
import time

import numpy as np
import pytest

from garage_tpu.ops.codec import (BlockCodec, CodecParams, IncrementalHash,
                                  hash_stream, mhash_stream)
from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.device_pool import DevicePool
from garage_tpu.ops.feeder import CodecFeeder
from garage_tpu.ops.hybrid_codec import HybridCodec
from garage_tpu.ops.transport import DeviceTransport, TransportItem
from garage_tpu.testing.synthetic_device import SyntheticLinkCodec
from garage_tpu.utils.data import Hash, blake2s_sum, blake2sum
from garage_tpu.utils.metrics import MetricsRegistry

K, M = 4, 2
RAGGED_SIZES = (4096, 1000, 4096, 256, 2048, 77, 3000, 1025)


def _params(**kw):
    kw.setdefault("rs_data", K)
    kw.setdefault("rs_parity", M)
    kw.setdefault("block_size", 4096)
    return CodecParams(**kw)


def _blocks(n=8, seed=0, sizes=RAGGED_SIZES):
    rng = np.random.default_rng(seed)
    out = [rng.integers(0, 256, (sizes[i % len(sizes)],),
                        dtype=np.uint8).tobytes() for i in range(n)]
    hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
              for b in out]
    return out, hashes


def _pooled_transport(link=100.0, pool_bytes=64 << 20, page_bytes=1024,
                      metrics=None, params=None, kind="synthetic"):
    """A transport over a pool on the synthetic device, or (`kind`
    "tpu") on the real device codec with the CPU's devices: the pool's
    two programs as the chip runs them."""
    p = params or _params()
    cpu = CpuCodec(p)
    if kind == "tpu":
        from garage_tpu.ops.tpu_codec import TpuCodec

        dev = TpuCodec(p, metrics=metrics)
        obs = dev.obs
    else:
        dev = SyntheticLinkCodec(p, link_gibs=link, compute_real=True)
        obs = None
    pool = DevicePool(dev, pool_bytes=pool_bytes, page_bytes=page_bytes,
                      metrics=metrics)
    tr = DeviceTransport(dev, p, fallback=cpu, observer=obs,
                         metrics=metrics, pool=pool)
    return tr, pool, dev, cpu


DEVICES = ("synthetic", "tpu")


class _TableOnly:
    """A device that holds no bytes: the page table alone is under test."""

    def pool_alloc(self, npages, page_bytes):
        return "pages"

    def pool_adopt(self, pool, batch, dst):
        return pool


def _table(pool_bytes, page_bytes=1024):
    return DevicePool(_TableOnly(), pool_bytes=pool_bytes,
                      page_bytes=page_bytes)


def _adopt(pool, key, length):
    """One block through `adopt_lanes`, as lane 0 of a batch one row
    wide enough for it: → whether it is resident afterwards."""
    pool.adopt_lanes(None, 1, pool.bytes_for(length), [(0, key, length)])
    return pool.contains(key)


def _bytes(device_array) -> np.ndarray:
    """A batch or the page array as the bytes it holds (the device
    codec keeps both as little-endian words)."""
    return np.asarray(device_array).view(np.uint8)


def _by_hand(tr, blocks, hashes, slot=0):
    """Dispatch one scrub batch on the caller's thread, through the
    transport's own staging and submission: → (batch, handle), for
    `tr._collect` whenever the test wants the collect to happen."""
    it = TransportItem("scrub", (blocks, hashes), len(blocks),
                       sum(map(len, blocks)))
    (batch,) = tr._plan("scrub", [it])
    return batch, tr._submit(batch, tr._stage(batch, slot))


def _scrub(tr, blocks, hashes, want_parity=True, timeout=30):
    it = TransportItem("scrub", (blocks, hashes), len(blocks),
                       sum(map(len, blocks)), want_parity=want_parity)
    tr.submit_items("scrub", [it])
    return it.future.result(timeout=timeout)


# --- hit/miss accounting: every scrubbed byte attributed exactly --------


def test_hit_miss_accounting_exact_cold_then_warm():
    reg = MetricsRegistry()
    tr, pool, dev, cpu = _pooled_transport(metrics=reg)
    blocks, hashes = _blocks(n=9)
    total = sum(map(len, blocks))

    ok1, par1 = _scrub(tr, blocks, hashes)
    assert ok1.all()
    st = pool.stats()
    assert st["miss_bytes"] == total and st["hit_bytes"] == 0
    assert st["resident_blocks"] == len(blocks)
    cold_staged = tr.staged_bytes
    assert cold_staged == total  # the cold pass paid the link in full

    ok2, par2 = _scrub(tr, blocks, hashes)
    assert ok2.all()
    st = pool.stats()
    # the invariant the dashboards divide by: hit + miss == bytes scrubbed
    assert st["hit_bytes"] + st["miss_bytes"] == 2 * total
    assert st["hit_bytes"] == total
    # a full pool hit moves ZERO link bytes — staged counter stays flat
    assert tr.staged_bytes == cold_staged
    body = reg.render()
    assert "pool_hit_bytes_total" in body and "pool_miss_bytes_total" in body
    # warm results stay bit-identical to the CPU reference
    rok, rpar = cpu.scrub_encode_batch(blocks, hashes, True)
    assert ok2.tolist() == rok.tolist()
    assert par2.shape == rpar.shape and (par2 == rpar).all()
    tr.shutdown()


def test_partial_residency_splits_bytes_exactly():
    tr, pool, dev, _cpu = _pooled_transport()
    blocks, hashes = _blocks(n=8)
    _scrub(tr, blocks, hashes)
    # knock two entries out: their next scrub is a miss, the rest hit
    dropped = [2, 5]
    for i in dropped:
        assert pool.invalidate(bytes(hashes[i]), reason="delete")
    before = pool.stats()
    ok, _p = _scrub(tr, blocks, hashes)
    assert ok.all()
    st = pool.stats()
    miss = sum(len(blocks[i]) for i in dropped)
    hit = sum(map(len, blocks)) - miss
    assert st["miss_bytes"] - before["miss_bytes"] == miss
    assert st["hit_bytes"] - before["hit_bytes"] == hit
    # the missed blocks were re-adopted on the way through
    assert st["resident_blocks"] == len(blocks)
    tr.shutdown()


# --- ragged occupancy: tail pages bit-identical -------------------------


@pytest.mark.parametrize("kind", DEVICES)
def test_ragged_tail_readback_bit_identical(kind):
    tr, pool, dev, _cpu = _pooled_transport(page_bytes=1024, kind=kind)
    # RAGGED_SIZES, 77 B .. 4096 B: lanes of 1, 2, 3 and 4 pages, and
    # lengths (77, 1025) that are no multiple of 4
    blocks, hashes = _blocks(n=8)
    assert {pool.pages_for(len(b)) for b in blocks} == {1, 2, 3, 4}
    _scrub(tr, blocks, hashes)
    for b, h in zip(blocks, hashes):
        got = pool.read(bytes(h))
        assert got == b, f"ragged readback mismatch at length {len(b)}"
    # geometry: a block spans ceil(len/page) pages, budget charges whole
    # pages (the 77 B block still claims one full page)
    assert pool.pages_for(77) == 1 and pool.bytes_for(77) == 1024
    assert pool.pages_for(1025) == 2 and pool.pages_for(4096) == 4
    assert pool.resident_bytes == sum(
        pool.bytes_for(len(b)) for b in blocks)
    tr.shutdown()


# --- scrub-cycle LRU ----------------------------------------------------


def test_lru_evicts_in_cycle_order():
    # unit-level: the page table alone, no device bytes needed
    pool = _table(4096)
    assert _adopt(pool, b"a" * 32, 1000)    # cycle 0
    pool.tick()
    assert _adopt(pool, b"b" * 32, 2000)    # cycle 1
    pool.tick()
    # needs 2 pages; only 1 free → the oldest-cycle entry goes first
    assert _adopt(pool, b"c" * 32, 2000)    # cycle 2
    assert not pool.contains(b"a" * 32)
    assert pool.contains(b"b" * 32) and pool.contains(b"c" * 32)
    assert pool.stats()["evicted_lru"] == 1
    # every page is some entry's or free, and none is both
    held = [s for e in pool._entries.values() for s in e.slots]
    assert sorted(held + pool._free) == list(range(pool.npages))


def test_lookup_bumps_recency_within_budget():
    pool = _table(3072)
    _adopt(pool, b"a" * 32, 1000)
    _adopt(pool, b"b" * 32, 1000)
    pool.tick()
    # touching `a` in the new cycle makes `b` the LRU victim
    assert pool.lookup(b"a" * 32, 1000) is not None
    _adopt(pool, b"c" * 32, 2000)
    assert pool.contains(b"a" * 32) and pool.contains(b"c" * 32)
    assert not pool.contains(b"b" * 32)
    # contains() must NOT bump (the prefetch filter would otherwise
    # distort eviction order)
    assert pool.stats()["evicted_lru"] == 1


def test_oversized_block_refused():
    pool = _table(2048)
    assert not _adopt(pool, b"x" * 32, 3000)
    assert pool.resident_bytes == 0 and len(pool._free) == 2


def test_a_batch_larger_than_the_pool_keeps_its_last_lanes():
    """One adoption of more pages than the budget: the batch's first
    lanes are evicted for its last, as lane-by-lane adoption did, and
    no slot is handed to two lanes of the one scatter."""
    seen = []

    class Recording(_TableOnly):
        def pool_adopt(self, pool, batch, dst):
            seen.append(dst.copy())
            return pool

    pool = DevicePool(Recording(), pool_bytes=4096, page_bytes=1024)
    keys = [bytes([i]) * 32 for i in range(3)]
    lanes, pages = pool.adopt_lanes(
        None, 4, 2048, [(i, k, 2000) for i, k in enumerate(keys)])
    assert (lanes, pages) == (2, 4)
    assert [pool.contains(k) for k in keys] == [False, True, True]
    (dst,) = seen
    kept = dst[dst < pool.npages]
    assert len(kept) == len(set(kept)) == 4 and (dst[:2] == 4).all()
    # a device that fails the scatter leaves nothing servable behind

    class Failing(_TableOnly):
        def pool_adopt(self, pool, batch, dst):
            raise RuntimeError("device lost")

    pool = DevicePool(Failing(), pool_bytes=4096, page_bytes=1024)
    with pytest.raises(RuntimeError):
        pool.adopt_lanes(None, 1, 1024, [(0, keys[0], 1000)])
    assert not pool.contains(keys[0]) and len(pool._free) == 4


# --- strict synchronous invalidation ------------------------------------


def test_post_invalidate_read_is_a_miss():
    tr, pool, dev, _cpu = _pooled_transport()
    blocks, hashes = _blocks(n=4)
    _scrub(tr, blocks, hashes)
    key = bytes(hashes[1])
    assert pool.read(key) == blocks[1]
    # every drop path the store acks flows through invalidate() with its
    # reason; the call is synchronous — on return, nothing is servable
    for reason in ("delete", "quarantine", "rebalance", "overwrite"):
        assert pool.invalidate(key, reason=reason) is (reason == "delete")
        assert pool.read(key) is None
    before = pool.stats()
    ok, _p = _scrub(tr, blocks, hashes)
    assert ok.all()
    st = pool.stats()
    assert st["miss_bytes"] - before["miss_bytes"] == len(blocks[1])
    assert st["invalidated"] == 1
    tr.shutdown()


@pytest.mark.parametrize("kind", DEVICES)
def test_corrupt_lane_never_adopted(kind):
    """A lane that fails the device hash verify must not become a
    servable page — adoption is gated on the per-lane ok bit."""
    tr, pool, dev, _cpu = _pooled_transport(kind=kind)
    blocks, hashes = _blocks(n=4)
    bad = list(blocks)
    bad[2] = b"\x00" + bad[2][1:]
    ok, _p = _scrub(tr, bad, hashes)
    assert not ok[2] and ok[0] and ok[1] and ok[3]
    assert pool.read(bytes(hashes[2])) is None
    assert pool.stats()["resident_blocks"] == 3
    tr.shutdown()


@pytest.mark.parametrize("kind", DEVICES)
def test_invalidate_between_dispatch_and_collect(kind):
    """`invalidate` is a page-table operation: the key is gone when it
    returns, the slots it frees are written by a later adopt only, and
    a batch composed before that adopt is what it was."""
    tr, pool, dev, _cpu = _pooled_transport(kind=kind, pool_bytes=8192)
    a, ha = _blocks(n=4, sizes=(2048,))             # fills the 8 pages
    batch, handle = _by_hand(tr, a, ha)
    assert tr._collect(batch, handle)[0][0].all()
    slots_a = {s for h in ha for s in pool.lookup(bytes(h), 2048).slots}
    assert slots_a == set(range(8))
    # dispatched with all four lanes served from the pool ...
    batch_a, handle_a = _by_hand(tr, a, ha, slot=0)
    assert batch_a.pool_hits == 4
    # ... and invalidated before its collect: synchronously gone
    for h in ha[:2]:
        assert pool.invalidate(bytes(h), reason="delete")
        assert not pool.contains(bytes(h)) and pool.read(bytes(h)) is None
    # a later adopt takes the freed slots
    b, hb = _blocks(n=2, seed=9, sizes=(2048,))
    batch_b, handle_b = _by_hand(tr, b, hb, slot=1)
    assert tr._collect(batch_b, handle_b)[0][0].all()
    slots_b = {s for h in hb for s in pool.lookup(bytes(h), 2048).slots}
    assert slots_b < slots_a and len(slots_b) == 4
    assert [pool.read(bytes(h)) for h in hb] == b
    # the batch composed before it still holds a's bytes and verifies
    (out_a, full_a), _spans = handle_a
    assert [bytes(r) for r in _bytes(full_a)[:4, :2048]] == a
    ok, _par = tr._collect(batch_a, handle_a)[0]
    assert ok.all()
    assert [pool.contains(bytes(h)) for h in ha] == [False, False, True, True]
    tr.shutdown()


# --- the two programs: one each a batch, from a closed set --------------


def _compose_lane_by_lane(miss_arr, miss_rows, lanes, cols, resident):
    """The composition as it was before the page array: zeros, the miss
    rows scattered, then each resident lane's pages concatenated, padded
    and set — the plain reference of the one-program composition."""
    full = np.zeros((lanes, cols), dtype=np.uint8)
    for i, r in enumerate(miss_rows):
        full[r] = miss_arr[i]
    for r, pages, _length in resident:
        row = np.concatenate(list(pages))
        if row.shape[0] < cols:
            row = np.pad(row, (0, cols - row.shape[0]))
        full[int(r)] = row[:cols]
    return full


def test_composed_batch_equals_lane_by_lane_composition():
    tr, pool, dev, _cpu = _pooled_transport(kind="tpu")
    blocks, hashes = _blocks(n=16)                  # ragged, 77 B .. 4 KiB
    batch, handle = _by_hand(tr, blocks, hashes)
    assert tr._collect(batch, handle)[0][0].all()
    # next: every third block is a miss again, and there are new ones
    for h in hashes[::3]:
        pool.invalidate(bytes(h))
    more, more_h = _blocks(n=5, seed=4)
    blocks, hashes = blocks + more, hashes + more_h
    pages = _bytes(pool.array()).reshape(pool.npages, -1)
    resident = [(r, [pages[s] for s in e.slots], len(b))
                for r, (b, e) in enumerate(
                    (b, pool._entries.get(bytes(h)))
                    for b, h in zip(blocks, hashes)) if e is not None]
    assert 0 < len(resident) < len(blocks)
    it = TransportItem("scrub", (blocks, hashes), len(blocks),
                       sum(map(len, blocks)))
    (batch,) = tr._plan("scrub", [it])
    miss_arr, miss_rows, lengths, _expected, _spans = staged = \
        tr._stage(batch, 0)
    (_out, full), _spans = tr._submit(batch, staged)
    lanes, cols = int(lengths.shape[0]), int(miss_arr.shape[1])
    assert miss_arr.shape[0] == lanes > len(miss_rows)      # a bucket
    want = _compose_lane_by_lane(miss_arr, miss_rows, lanes, cols, resident)
    assert np.array_equal(_bytes(full), want)
    assert [bytes(want[r, :len(b)]) for r, b in enumerate(blocks)] == blocks
    tr.shutdown()


def test_one_program_a_batch_each_way_from_a_closed_set():
    """A scripted pass of batches with 256, 0, 1, 17, 255 and 256
    misses: one `compose` a dispatch, one `adopt` a collect with
    misses, all of them members of the set the first batch of the
    geometry compiled — nothing is built or loaded after it."""
    from garage_tpu.ops.device_pool import miss_bucket, miss_buckets

    assert miss_buckets(256) == [0, 32, 64, 96, 128, 160, 192, 224, 256]
    assert miss_buckets(64) == [0, 32, 64] and miss_buckets(8) == [0, 8]
    assert [miss_bucket(n, 256) for n in (0, 1, 17, 255, 256)] == \
        [0, 32, 32, 256, 256]
    assert all(miss_bucket(n, lanes) in miss_buckets(lanes)
               and n <= miss_bucket(n, lanes) < n + max(32, lanes // 8)
               for lanes in (8, 24, 64, 256, 1024)
               for n in range(lanes + 1))
    reg = MetricsRegistry()
    tr, pool, dev, _cpu = _pooled_transport(
        kind="tpu", metrics=reg, page_bytes=512, pool_bytes=1 << 20,
        params=_params(block_size=1024))
    blocks, hashes = _blocks(n=256, sizes=(1024, 512, 900, 37))
    programs = reg.counter("pool_programs_total")
    compiles = reg.counter("codec_compiles_total")

    def compiled():
        return sum(compiles._vals.values())

    def scrub_with_misses(n):
        for h in hashes[:n]:
            pool.invalidate(bytes(h))
        before = {op: programs.get(op=op) for op in ("compose", "adopt")}
        ok, _par = _scrub(tr, blocks, hashes, timeout=300)
        assert ok.all()
        return {op: programs.get(op=op) - before[op] for op in before}

    assert scrub_with_misses(0) == {"compose": 1, "adopt": 1}   # cold: 256
    closed = {("alloc", pool.npages, 512)} | set(
        dev.pool_program_keys(256, 1024))
    assert set(dev._pool_execs) == closed and len(closed) == 12
    warm = compiled()
    assert warm > 0
    for n in (0, 1, 17, 255, 256):
        assert scrub_with_misses(n) == {"compose": 1, "adopt": int(n > 0)}
    # rows of the parity named: the geometry's one row program, a
    # dispatch a row, built with the rest of the set
    ok, par = _scrub(tr, blocks, hashes, want_parity=[31, 2], timeout=300)
    assert ok.all() and sorted(par) == [2, 31]
    assert programs.get(op="parity_row") == 2
    assert compiled() == warm and set(dev._pool_execs) == closed
    assert programs.get(op="alloc") == 1
    # the bucket's pad rows are counted as pad, not as payload
    lane_bytes = reg.counter("transport_lane_bytes_total")
    assert lane_bytes.get(kind="scrub", part="payload") == tr.staged_bytes
    assert lane_bytes.get(kind="scrub", part="pad") > 0
    tr.shutdown()


# --- prefetch: staged ahead of need, visible in the timeline ------------


def test_prefetch_stages_ahead_and_overlaps_compute():
    # slow link so device windows are wide enough for the pipelined
    # staging to land inside them; a blocker batch keeps the worker
    # busy while BOTH the foreground batch and the prefetch enqueue, so
    # the double buffer deterministically stages one during the other's
    # compute (the test_transport blocker idiom)
    tr, pool, dev, _cpu = _pooled_transport(link=0.02)
    bl_blocks, bl_hashes = _blocks(n=K * 32, seed=3, sizes=(4096,))
    blocker = TransportItem("scrub", (bl_blocks, bl_hashes),
                            len(bl_blocks), sum(map(len, bl_blocks)))
    tr.submit_items("scrub", [blocker])
    fg_blocks, fg_hashes = _blocks(n=K * 4, seed=1, sizes=(4096,))
    pf_blocks, pf_hashes = _blocks(n=K * 2, seed=2, sizes=(4096,))
    it = TransportItem("scrub", (fg_blocks, fg_hashes), len(fg_blocks),
                       sum(map(len, fg_blocks)))
    tr.submit_items("scrub", [it])
    nbytes = tr.prefetch(pf_blocks, pf_hashes)
    assert nbytes == sum(map(len, pf_blocks))
    ok, _p = it.future.result(timeout=60)
    assert ok.all()
    # wait out the background prefetch batch
    deadline = time.monotonic() + 30
    while (time.monotonic() < deadline
           and pool.stats()["resident_blocks"] < len(pf_blocks)):
        time.sleep(0.02)
    st = pool.stats()
    assert st["resident_blocks"] >= len(pf_blocks)
    # prefetch bytes ride their OWN family: hit+miss still equals the
    # bytes scrub itself asked for (zero so far for the pf range)
    assert st["prefetch_bytes"] == sum(map(len, pf_blocks))
    assert st["miss_bytes"] == sum(map(len, fg_blocks)) + \
        sum(map(len, bl_blocks))
    # the timeline shows the prefetch: the hint instant on the edf
    # track, and the prefetch batch's staging/compute windows (flagged
    # prefetch=True) overlapping a real batch's windows — the double
    # buffer hiding the prefetch link work under foreground compute
    evs = tr.obs.timeline.snapshot()
    hints = [e for e in evs if e["name"] == "pool_prefetch"]
    assert hints, "prefetch hint instant missing from timeline"

    def _windows(prefetch):
        return [e for e in evs
                if e["name"] in ("stage scrub", "compute scrub")
                and bool(e.get("args", {}).get("prefetch")) is prefetch]

    pf_win, real_win = _windows(True), _windows(False)
    assert pf_win, "prefetch windows missing from timeline"
    assert real_win, "non-prefetch windows missing from timeline"

    def _overlaps(a, b):
        a0, a1 = a["ts"], a["ts"] + a.get("dur", 0)
        b0, b1 = b["ts"], b["ts"] + b.get("dur", 0)
        return a0 < b1 and b0 < a1

    assert any(_overlaps(s, w) for s in pf_win for w in real_win), \
        "prefetch did not overlap any real batch window"
    # second act: the prefetched range scrubs as a pure pool hit
    staged = tr.staged_bytes
    ok2, _ = _scrub(tr, pf_blocks, pf_hashes)
    assert ok2.all()
    assert tr.staged_bytes == staged
    assert pool.stats()["hit_bytes"] == sum(map(len, pf_blocks))
    tr.shutdown()


def test_prefetch_filters_resident_blocks():
    tr, pool, dev, _cpu = _pooled_transport()
    blocks, hashes = _blocks(n=6)
    _scrub(tr, blocks, hashes)
    # everything already resident: the hint is a no-op, zero bytes
    assert tr.prefetch(blocks, hashes) == 0
    tr.shutdown()


# --- pool disabled: byte-identical legacy behavior ----------------------


def test_pool_disabled_is_byte_identical_legacy():
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    cpu = CpuCodec(p)
    tr = DeviceTransport(dev, p, fallback=cpu)  # no pool
    blocks, hashes = _blocks(n=8)
    total = sum(map(len, blocks))
    for _ in range(2):
        ok, par = _scrub(tr, blocks, hashes)
        assert ok.all()
    rok, rpar = cpu.scrub_encode_batch(blocks, hashes, True)
    assert ok.tolist() == rok.tolist()
    assert par.shape == rpar.shape and (par == rpar).all()
    # every pass pays the link in full — exactly the pre-pool contract
    assert tr.staged_bytes == 2 * total
    assert tr.stats()["pool"] is None
    tr.shutdown()


def test_pool_mib_zero_disables_pool_in_hybrid():
    p = _params(pool_mib=0)
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    hy._probe_link()
    assert hy.transport is not None
    assert hy.pool is None and hy.transport.pool is None
    assert "pool" not in hy.info()
    hy.close()


def test_hybrid_arms_pool_by_default():
    p = _params()  # pool_mib defaults on
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    hy._probe_link()
    assert hy.pool is not None and hy.transport.pool is hy.pool
    assert hy.info()["pool"]["pool_bytes"] == p.pool_mib << 20
    hy.close()


# --- satellite: feeder gate-refresh short-circuit -----------------------


def test_fully_resident_bg_batch_skips_gate_probe():
    """A purely-background batch the pool would fully serve routes to
    the device WITHOUT paying the cold gate-refresh probe (the 16 MiB
    probe outweighs a zero-link-byte batch by orders of magnitude):
    with a STALE gate verdict, the pooled route fires and _probe_link
    is never called."""
    p = _params(pool_page_kib=1)
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    hy._probe_link()
    f = CodecFeeder(hy, slo_ms=20.0, max_batch_blocks=10_000)
    try:
        blocks, hashes = _blocks(n=8, sizes=(4096,))
        assert f.prefetch_scrub(blocks, hashes) > 0
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and hy.pool.stats()["resident_blocks"] < len(blocks)):
            time.sleep(0.02)
        assert hy.pool.stats()["resident_blocks"] == len(blocks)
        # age the cached link verdict past the hard TTL: ragged_side()
        # now says "cpu", the state where the old code always paid the
        # refresh probe before a purely-background batch
        with hy._probe_lock:
            hy._link_ts -= hy._LINK_PROBE_TTL_MAX_S + 1.0
        assert hy.ragged_side() == "cpu"
        probes = []
        orig_probe = hy._probe_link
        hy._probe_link = lambda: probes.append(1) or orig_probe()
        ok, _par = f.submit_scrub(blocks, hashes,
                                  want_parity=False).result(timeout=30)
        assert all(map(bool, ok))
        assert not probes, "resident bg batch still paid the gate probe"
        routes = [e for e in hy.obs.events_list(256)
                  if e.get("kind") == "feeder_route"
                  and e.get("reason") == "pool_resident"]
        assert routes, "resident bg batch did not take the pool route"
        assert hy.pool.stats()["hit_bytes"] == sum(map(len, blocks))
    finally:
        f.shutdown()
        hy.close()


# --- satellite: O(1) incremental BLAKE2 hash state ----------------------


def test_incremental_hash_bit_identity_across_chunkings():
    rng = np.random.default_rng(7)
    body = rng.integers(0, 256, (1 << 20) + 37, dtype=np.uint8).tobytes()
    for chunks in ([len(body)], [1, 2, 3, len(body) - 6],
                   [65536] * (len(body) // 65536) + [len(body) % 65536]):
        hs, hm = hash_stream(), mhash_stream()
        off = 0
        for n in chunks:
            hs.update(body[off:off + n])
            hm.update(body[off:off + n])
            off += n
        assert off == len(body)
        assert hs.nbytes == hm.nbytes == len(body)
        # bit-identical to the one-shot digests the store keys on
        assert bytes(hs.digest()) == bytes(blake2s_sum(body))
        assert bytes(hm.digest()) == bytes(blake2sum(body))
        assert hm.hexdigest() == bytes(blake2sum(body)).hex()


def test_incremental_hash_copy_is_independent():
    h = mhash_stream()
    h.update(b"abc")
    fork = h.copy()
    fork.update(b"def")
    h.update(b"xyz")
    assert bytes(h.digest()) == bytes(blake2sum(b"abcxyz"))
    assert bytes(fork.digest()) == bytes(blake2sum(b"abcdef"))
    assert isinstance(h, IncrementalHash)


def test_codec_exposes_stream_hashers():
    codec = BlockCodec(_params())
    hs, hm = codec.hash_stream(), codec.mhash_stream()
    hs.update(b"block")
    hm.update(b"block")
    assert bytes(hs.digest()) == bytes(blake2s_sum(b"block"))
    assert bytes(hm.digest()) == bytes(blake2sum(b"block"))


# --- exposition hygiene -------------------------------------------------


def test_pool_families_pass_promlint_and_metricsdoc():
    from garage_tpu.utils.metricsdoc import undocumented_families
    from garage_tpu.utils.promlint import lint_exposition

    reg = MetricsRegistry()
    tr, pool, dev, _cpu = _pooled_transport(metrics=reg, pool_bytes=8192,
                                            page_bytes=1024)
    blocks, hashes = _blocks(n=8)
    _scrub(tr, blocks, hashes)     # misses + adoptions (+ lru evictions)
    _scrub(tr, blocks, hashes)     # hits
    pool.invalidate(bytes(hashes[0]), reason="delete")
    tr.prefetch(blocks[:1], hashes[:1])
    deadline = time.monotonic() + 10
    while (time.monotonic() < deadline
           and pool.stats()["prefetch_bytes"] == 0):
        time.sleep(0.02)
    body = reg.render()
    for fam in ("pool_hit_bytes_total", "pool_miss_bytes_total",
                "pool_prefetch_bytes_total", "pool_evict_total",
                "pool_resident_bytes", "pool_pages"):
        assert fam in body, f"{fam} missing from exposition"
    assert lint_exposition(body) == [], lint_exposition(body)
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                            "OBSERVABILITY.md")).read()
    assert undocumented_families(body, doc) == []
    tr.shutdown()

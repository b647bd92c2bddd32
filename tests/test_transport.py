"""DeviceTransport — the zero-copy colocated device queue (ISSUE 11).

Covers the acceptance contract: bit-identical results through the
double-buffered staging path under concurrent producers, ≤ 1 host copy
per staged block, earliest-deadline-first dispatch with foreground
beating background at equal arrival, the staging-bound clamp (oversized
batches chunked at codeword boundaries and reassembled exactly), a dead
device degrading to inline CPU with zero caller-visible errors, the
single-producer property (scrub rides the SAME feeder queue as
foreground verifies — the device's bytes-level API is never touched),
the link-probe backoff fix (a recovered link re-probed within one
healthy TTL), the CPU encode-schedule cache, and promlint over the new
transport metric families.
"""

import hashlib
import threading
import time
import types

import numpy as np
import pytest

from garage_tpu.ops.codec import BlockCodec, CodecParams
from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.feeder import CodecFeeder
from garage_tpu.ops.hybrid_codec import HybridCodec
from garage_tpu.ops.transport import (DeviceTransport, TransportClosed,
                                      TransportItem)
from garage_tpu.testing.synthetic_device import SyntheticLinkCodec
from garage_tpu.utils.data import Hash
from garage_tpu.utils.metrics import MetricsRegistry

K, M = 4, 2


def _params(**kw):
    kw.setdefault("rs_data", K)
    kw.setdefault("rs_parity", M)
    kw.setdefault("block_size", 4096)
    return CodecParams(**kw)


def _blocks(n=8, seed=0, sizes=(4096, 1000, 4096, 256, 4096, 77)):
    rng = np.random.default_rng(seed)
    out = [rng.integers(0, 256, (sizes[i % len(sizes)],),
                        dtype=np.uint8).tobytes() for i in range(n)]
    hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
              for b in out]
    return out, hashes


def _transport(link=100.0, params=None, **tr_kw):
    p = params or _params()
    dev = SyntheticLinkCodec(p, link_gibs=link, compute_real=True)
    cpu = CpuCodec(p)
    return DeviceTransport(dev, p, fallback=cpu, **tr_kw), dev, cpu


# --- bit-identity under concurrent producers (double-buffered) ----------


def test_double_buffer_bit_identity_under_concurrent_producers():
    """Many threads submitting mixed kinds concurrently through the
    2-slot double-buffered staging path: every result is bit-identical
    to the serial CPU computation."""
    tr, dev, cpu = _transport()
    errs = []

    def producer(seed):
        try:
            blocks, hashes = _blocks(n=K * 2 + 1, seed=seed)
            ith = TransportItem("hash", blocks, len(blocks),
                                sum(map(len, blocks)))
            its = TransportItem("scrub", (blocks, hashes), len(blocks),
                                sum(map(len, blocks)))
            ite = TransportItem("encode", blocks, len(blocks),
                                sum(map(len, blocks)))
            tr.submit_items("hash", [ith])
            tr.submit_items("scrub", [its])
            tr.submit_items("encode", [ite])
            got = ith.future.result(timeout=30)
            assert [bytes(g) for g in got] == \
                [bytes(h) for h in hashes], "hash mismatch"
            ok, par = its.future.result(timeout=30)
            rok, rpar = cpu.scrub_encode_batch(blocks, hashes, True)
            assert ok.tolist() == rok.tolist()
            assert par.shape == rpar.shape and (par == rpar).all()
            enc = ite.future.result(timeout=30)
            renc = cpu.rs_encode_blocks(blocks)
            assert enc.shape == renc.shape and (enc == renc).all()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(s,))
               for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    assert tr.dispatches > 0
    tr.shutdown()


def test_decode_through_transport_matches_cpu():
    tr, dev, cpu = _transport()
    blocks, _h = _blocks(n=K, sizes=(4096,))
    shards = np.stack([np.frombuffer(b, dtype=np.uint8)
                       for b in blocks]).reshape(1, K, 4096)
    parity = cpu.rs_encode(shards)
    present = [0, 1, K, K + 1]
    surv = np.ascontiguousarray(np.concatenate(
        [shards[:, [0, 1], :], parity[:, :2, :]], axis=1))
    it = TransportItem("decode", (surv, present, [2, 3]), 1,
                       int(surv.nbytes))
    tr.submit_items("decode", [it])
    dec = it.future.result(timeout=30)
    assert (dec == shards[:, 2:4, :]).all()
    tr.shutdown()


# --- the copy counter (the zero-copy claim's proof) ---------------------


def test_copy_counter_at_most_one_copy_per_block():
    reg = MetricsRegistry()
    tr, dev, cpu = _transport(metrics=reg)
    blocks, hashes = _blocks(n=16)
    for _ in range(3):
        it = TransportItem("scrub", (blocks, hashes), len(blocks),
                           sum(map(len, blocks)))
        tr.submit_items("scrub", [it])
        ok, _p = it.future.result(timeout=30)
        assert ok.all()
    assert tr.staged_blocks == 48
    assert tr.copies_per_block() <= 1.0, tr.stats()
    # the metric carries the same claim, labelled with the copy count
    assert 'transport_staged_bytes_total{copies="1"}' in reg.render()
    # the bytes-level (serialize+copy) device path was never used
    assert dev.submissions == 0 and dev.host_copies == 0
    tr.shutdown()


# --- deadline-ordered dispatch ------------------------------------------


def test_foreground_beats_background_at_equal_arrival():
    """With the worker busy on a blocker batch, a background batch
    enqueued BEFORE a foreground one is still dispatched after it —
    the EDF heap demotes background by the governor-scaled slack."""
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=0.05, compute_real=True)
    order = []
    orig = dev.scrub_encode_submit

    def spy(arr, lengths, expected):
        order.append(int(np.count_nonzero(lengths)))
        return orig(arr, lengths, expected)

    dev.scrub_encode_submit = spy
    tr = DeviceTransport(dev, p, fallback=CpuCodec(p))
    tr.slots, tr._slot_bufs, tr._slot_free = 1, [None], [0]
    bl, h = _blocks(n=K)        # blocker: K blocks
    bg_b, bg_h = _blocks(n=2 * K)   # background: 2K blocks
    fg_b, fg_h = _blocks(n=3 * K)   # foreground: 3K blocks
    blocker = TransportItem("scrub", (bl, h), K, sum(map(len, bl)))
    tr.submit_items("scrub", [blocker])
    deadline = time.monotonic() + 5
    while not tr._inflight and time.monotonic() < deadline:
        time.sleep(0.002)   # worker must hold the only slot
    bg = TransportItem("scrub", (bg_b, bg_h), 2 * K,
                       sum(map(len, bg_b)), cls="bg")
    tr.submit_items("scrub", [bg])
    fg = TransportItem("scrub", (fg_b, fg_h), 3 * K,
                       sum(map(len, fg_b)), cls="fg")
    tr.submit_items("scrub", [fg])
    fg.future.result(timeout=60)
    bg.future.result(timeout=60)
    assert order == [K, 3 * K, 2 * K], \
        f"dispatch order (by block count) was {order}"
    tr.shutdown()


def test_governor_pressure_stretches_background_slack():
    tr, dev, cpu = _transport()
    ratio = [1.0]
    tr.governor_ratio = lambda: ratio[0]
    from garage_tpu.ops.transport import _Batch

    b = _Batch("scrub", "bg")
    now = 100.0
    full = tr._effective_deadline(b, now) - now
    ratio[0] = 0.1
    throttled = tr._effective_deadline(b, now) - now
    assert throttled == pytest.approx(full * 10)
    # foreground is always scheduled at arrival
    f = _Batch("scrub", "fg")
    assert tr._effective_deadline(f, now) == now
    tr.shutdown()


# --- staging-bound clamp ------------------------------------------------


def test_staging_bound_clamps_and_reassembles_bit_identically():
    """A scrub batch far larger than the staging budget is cut at
    codeword-aligned boundaries, never stages more than the budget at
    once, and reassembles (ok, parity) bit-identically."""
    tr, dev, cpu = _transport()
    tr.chunk_bytes = 16 << 10
    tr.budget_bytes = 32 << 10
    blocks, hashes = _blocks(n=K * 16, sizes=(4096,))
    it = TransportItem("scrub", (blocks, hashes), len(blocks),
                       sum(map(len, blocks)))
    tr.submit_items("scrub", [it])
    ok, par = it.future.result(timeout=60)
    rok, rpar = cpu.scrub_encode_batch(blocks, hashes, True)
    assert ok.tolist() == rok.tolist()
    assert par.shape == rpar.shape and (par == rpar).all()
    assert tr.chunks_split > 0, "oversized batch was not chunked"
    assert tr.max_staged_bytes_seen <= tr.budget_bytes, tr.stats()
    assert any(e["kind"] == "transport_chunk"
               for e in tr.obs.events_list())
    tr.shutdown()


# --- closed-device fallback ---------------------------------------------


def test_dead_device_degrades_to_inline_cpu_with_zero_errors():
    """Every submission against a device that dies at submit resolves
    with the CPU result — no caller-visible error — and after the
    failure limit the transport closes so the feeder routes around it."""
    p = _params()

    class _Dead(SyntheticLinkCodec):
        def scrub_encode_submit(self, *a):
            raise RuntimeError("device gone")

    dev = _Dead(p, link_gibs=100.0, compute_real=True)
    cpu = CpuCodec(p)
    tr = DeviceTransport(dev, p, fallback=cpu)
    blocks, hashes = _blocks(n=K * 2)
    rok, rpar = cpu.scrub_encode_batch(blocks, hashes, True)
    for i in range(4):
        it = TransportItem("scrub", (blocks, hashes), len(blocks),
                           sum(map(len, blocks)))
        try:
            tr.submit_items("scrub", [it])
        except TransportClosed:
            assert i >= 3, "transport closed before the failure limit"
            break
        ok, par = it.future.result(timeout=30)
        assert ok.tolist() == rok.tolist()
        assert (par == rpar).all()
    assert tr.fallbacks >= 3
    assert not tr.alive, "transport must close after repeated failures"
    assert any(e["kind"] == "transport_down"
               for e in tr.obs.events_list())
    tr.shutdown()


def test_staged_hash_absorbed_in_place_on_device_failure():
    """A hash batch whose device dies AT SUBMIT (after staging) is
    hashed straight off the lane-aligned staging rows — digests
    bit-identical to hashlib, rows consumed in place (the SIMD-friendly
    staging-layout contract), and the staging stride is 64-aligned."""
    p = _params()

    class _DeadHash(SyntheticLinkCodec):
        def hash_submit(self, arr, lengths):
            # prove the absorb used THIS staging buffer: remember it
            self.seen = (arr, lengths)
            raise RuntimeError("device gone")

    dev = _DeadHash(p, link_gibs=100.0, compute_real=True)
    cpu = CpuCodec(p)
    tr = DeviceTransport(dev, p, fallback=cpu)
    blocks, hashes = _blocks(n=6)
    it = TransportItem("hash", blocks, len(blocks), sum(map(len, blocks)))
    tr.submit_items("hash", [it])
    digs = it.future.result(timeout=30)
    assert [bytes(d) for d in digs] == [bytes(h) for h in hashes]
    arr, _lengths = dev.seen
    assert arr.shape[1] % DeviceTransport.HASH_ROW_ALIGN == 0, arr.shape
    assert tr.fallbacks == 1
    tr.shutdown()


def test_feeder_routes_inline_when_transport_closed():
    """The feeder's dispatch falls back to the inline (CPU) ragged path
    when the codec's transport is closed — shutdown races degrade, they
    never error."""
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    assert hy.transport is not None
    hy._probe_link()            # open the gate (cached verdict)
    hy.transport.shutdown()     # device path gone
    f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=64)
    blocks, hashes = _blocks(n=K)
    got = f.submit_hash(blocks).result(timeout=10)
    assert [bytes(g) for g in got] == [bytes(h) for h in hashes]
    ok, par = f.submit_scrub(blocks, hashes).result(timeout=10)
    assert ok.all() and par is not None
    f.shutdown()


# --- the single-producer property ---------------------------------------


def test_scrub_and_foreground_share_one_feeder_queue():
    """Background scrub batches and foreground verifies enter the device
    through the SAME feeder → transport queue: the device codec's
    bytes-level calls (the old behind-the-feeder's-back path) are
    never called, and both classes appear in the transport's meter."""
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    hy._probe_link()            # open the cached gate for ragged routing
    assert hy.ragged_side() == "tpu"
    f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)
    blocks, hashes = _blocks(n=K * 2)
    fut_fg = f.submit_hash(blocks, peers=1)
    fut_bg = f.submit_scrub(blocks, hashes, want_parity=True)
    got = fut_fg.result(timeout=30)
    ok, par = fut_bg.result(timeout=30)
    assert [bytes(g) for g in got] == [bytes(h) for h in hashes]
    assert ok.all() and par is not None
    assert dev.submissions == 0, \
        "scrub reached the device outside the transport queue"
    assert dev.array_submissions >= 2
    assert hy.transport.dispatches >= 2
    assert hy.obs.tpu_frac() > 0
    f.shutdown()
    hy.close()


@pytest.mark.asyncio
async def test_scrub_worker_batch_rides_the_feeder():
    """ScrubWorker.scrub_batch routes its fused verify+encode through
    mgr.feeder (class bg) instead of calling the codec directly."""
    from garage_tpu.block.repair import ScrubWorker

    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    hy._probe_link()
    feeder = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)

    class _Span:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    mgr = types.SimpleNamespace(
        codec=hy, feeder=feeder, parity_store=None, ec_accumulator=None,
        resync=None, corruptions=0,
        data_layout=types.SimpleNamespace(data_dirs=[]),
        system=types.SimpleNamespace(
            tracer=types.SimpleNamespace(span=lambda *a, **kw: _Span())),
    )
    worker = ScrubWorker(mgr)
    blocks, hashes = _blocks(n=K * 2)
    batch = [(h, f"/nonexistent/{i}", False)
             for i, h in enumerate(hashes)]
    await worker.scrub_batch(batch, reads=list(blocks))
    assert dev.submissions == 0, "scrub bypassed the feeder queue"
    assert dev.array_submissions >= 1
    assert feeder.stats()["dispatches"] >= 1
    feeder.shutdown()
    hy.close()


# --- probe path + backoff fix -------------------------------------------


def test_gate_opens_through_transport_probe_without_device_hook():
    """A device codec WITHOUT its own probe_link hook is probed through
    the transport (the new path); a healthy link opens the gate."""
    p = _params()

    class _NoHook(SyntheticLinkCodec):
        probe_link = None       # only the transport path remains

    dev = _NoHook(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    assert hy.transport is not None
    rate = hy._probe_link()
    assert rate >= p.hybrid_min_link_gibs
    assert hy.ragged_side() == "tpu"
    hy.close()


def test_probe_backoff_recovered_link_reprobed_within_one_ttl():
    """The satellite regression: a link measured below the gate
    threshold is re-probed within ONE healthy TTL — below-threshold
    measurements no longer ride the doubling fail-TTL ladder, so a
    recovered link reopens the gate at the next healthy-TTL probe."""
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=0.001, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    for _ in range(4):
        hy._link_ts = 0.0       # force the cache stale each round
        assert hy._probe_link() < p.hybrid_min_link_gibs
    assert hy._link_ttl == hy._LINK_PROBE_TTL_S, \
        "below-threshold probes must not double the healthy TTL"
    # the link recovers: within one TTL the next probe reopens the gate
    dev.link_gibs = 100.0
    hy._link_ts = time.monotonic() - hy._LINK_PROBE_TTL_S - 0.01
    assert hy._probe_link() >= p.hybrid_min_link_gibs
    assert hy.ragged_side() == "tpu"
    hy.close()


def test_probe_failure_ladder_still_backs_off_and_resets():
    """Probe FAILURES (exceptions) do ride a doubling ladder — a
    durably-dead backend is not hammered — and one healthy probe resets
    it."""
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    boom = [True]
    orig = dev.probe_link

    def flaky(nbytes):
        if boom[0]:
            raise RuntimeError("probe transport died")
        return orig(nbytes)

    dev.probe_link = flaky
    start_fail_ttl = hy._fail_ttl
    for i in range(3):
        hy._link_ts = 0.0
        hy._probe_link()
    assert hy._link_failed
    assert hy._fail_ttl == start_fail_ttl * 8, hy._fail_ttl
    boom[0] = False
    hy._link_ts = 0.0
    assert hy._probe_link() >= p.hybrid_min_link_gibs
    assert hy._fail_ttl == start_fail_ttl, \
        "a healthy probe must reset the failure ladder"
    hy.close()


def test_pending_scrub_does_not_stall_foreground_peers_window():
    """A co-pending background scrub (peers=None by design) must not
    disable the foreground peers short-circuit: with all K expected
    foreground submitters arrived, the window dispatches `peers`/`lone`
    instead of sleeping the full SLO."""
    p = _params()
    f = CodecFeeder(CpuCodec(p), slo_ms=5_000.0, max_batch_blocks=10_000)
    blocks, hashes = _blocks(n=K)
    fut_bg = f.submit_scrub(blocks, hashes)      # peers=None, cls=bg
    t0 = time.perf_counter()
    fut_fg = f.submit_hash(blocks, peers=1)
    got = fut_fg.result(timeout=10)
    dt = time.perf_counter() - t0
    assert [bytes(g) for g in got] == [bytes(h) for h in hashes]
    assert dt < 2.0, f"foreground waited {dt:.2f}s behind a scrub item"
    ok, _par = fut_bg.result(timeout=30)
    assert ok.all()
    reasons = f.stats()["dispatch_reasons"]
    assert reasons.get("lone", 0) >= 1, reasons
    f.shutdown()


def test_background_batch_refreshes_closed_gate():
    """With the gate unprobed (cold daemon), a BACKGROUND scrub batch
    pays the TTL-cached probe and re-opens the device route for itself
    (nobody else probes on its behalf).  Foreground-only traffic never
    probes cold."""
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    assert hy.ragged_side() == "cpu", "gate must start unprobed/closed"
    f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)
    blocks, hashes = _blocks(n=K)
    # foreground hash: stays on the CPU floor, no cold probe
    f.submit_hash(blocks, peers=1).result(timeout=10)
    assert hy._link_rate is None, "foreground paid a cold probe"
    # background scrub: probes, opens, rides the transport
    ok, par = f.submit_scrub(blocks, hashes).result(timeout=30)
    assert ok.all() and par is not None
    assert hy._link_rate is not None and hy.ragged_side() == "tpu"
    assert dev.array_submissions >= 1, "scrub did not reach the device"
    f.shutdown()
    hy.close()


# --- one contract for every road from a batch of blocks to its answer ---
#
# The work-stealing engine that used to sit beside these roads is gone;
# what its tests held of the SYSTEM is held here, on the roads that run:
# the ScrubWorker's (feeder → transport), the same with the gate shut
# (the feeder's inline CPU dispatch), and the bytes-level call a caller
# without a feeder makes (block/repair.py, FeederClosed in
# ops/feeder.py).  A submission wider than the staging bound being cut
# and reassembled is test_staging_bound_clamps_and_reassembles_bit_
# identically above.

_ROUTES = ("transport", "gate_shut", "no_feeder")
_CODECS = {"rs84": (8, 4), "rs42": (4, 2), "rep": (0, 0)}


def _scrub_by(route, hy, blocks, hashes):
    if route == "no_feeder":
        return hy.scrub_encode_batch(blocks, hashes, True)
    f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)
    try:
        return f.submit_scrub(blocks, hashes, True).result(timeout=30)
    finally:
        f.shutdown()


@pytest.mark.parametrize("codec", sorted(_CODECS))
@pytest.mark.parametrize("route", _ROUTES)
def test_every_road_answers_as_the_cpu_codec(route, codec):
    """`ok` and parity equal CpuCodec's for a batch with corrupt lanes
    and an unaligned ragged tail, and the batch ran where the route
    says: whole, on one side."""
    k, m = _CODECS[codec]
    p = _params(rs_data=k, rs_parity=m)
    dev = SyntheticLinkCodec(
        p, link_gibs=0.001 if route == "gate_shut" else 100.0,
        compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    blocks, hashes = _blocks(n=2 * max(k, 4) + 3, seed=11)
    blocks[1] = b"\xff" + blocks[1][1:]
    blocks[-1] = blocks[-1][:-1] + b"\x00"
    nbytes = sum(map(len, blocks))
    rok, rpar = CpuCodec(p).scrub_encode_batch(blocks, hashes, True)
    assert not rok[1] and not rok[-1] and rok.sum() == len(blocks) - 2
    try:
        # no_feeder: the bytes-level call takes the gate's probe itself
        ok, par = _scrub_by(route, hy, blocks, hashes)
    finally:
        hy.close()
    assert ok.tolist() == rok.tolist()
    if k == 0:
        assert par is None and rpar is None
    else:
        assert par.shape == rpar.shape and (par == rpar).all()
    side = "cpu" if route == "gate_shut" else "tpu"
    assert hy.last_gate == ("hold" if route == "gate_shut" else "open")
    assert hy.obs.bytes_total[side] == nbytes, hy.obs.bytes_total
    assert hy.obs.bytes_total["cpu" if side == "tpu" else "tpu"] == 0
    assert (dev.array_submissions, dev.submissions) == {
        "transport": (1, 0), "gate_shut": (0, 0), "no_feeder": (0, 1),
    }[route]


def test_coalesced_submissions_get_parity_of_their_own_blocks():
    """Two submissions whose sizes are not multiples of k, coalesced
    into ONE transport batch: each gets (ok, parity) of its own blocks
    only — no RS codeword straddles two submissions."""
    tr, dev, cpu = _transport()
    subs = [_blocks(n=11, seed=21), _blocks(n=13, seed=22)]
    subs[1][0][4] = b"\x00" * len(subs[1][0][4])
    items = [TransportItem("scrub", (b, h), len(b), sum(map(len, b)))
             for b, h in subs]
    tr.submit_items("scrub", items)
    for it, (b, h) in zip(items, subs):
        ok, par = it.future.result(timeout=30)
        rok, rpar = cpu.scrub_encode_batch(b, h, True)
        assert ok.tolist() == rok.tolist()
        assert par.shape == rpar.shape and (par == rpar).all()
    assert dev.array_submissions == 1, "the two were not coalesced"
    tr.shutdown()


@pytest.mark.parametrize("route", ("transport", "inline", "no_feeder"))
def test_a_device_that_raises_costs_no_error_on_any_road(route):
    """The device dies under the batch: the caller gets the CPU floor's
    answer and the ring names the failure.  `inline` is the feeder's
    dispatch through the bytes-level calls (no transport armed)."""
    p = _params(transport=route != "inline")

    class _Raises(SyntheticLinkCodec):
        def scrub_encode_batch(self, *a, **kw):
            raise RuntimeError("device gone")

        scrub_encode_submit = scrub_encode_batch
        scrub_encode_submit_resident = scrub_encode_batch

    dev = _Raises(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    assert (hy.transport is None) == (route == "inline")
    hy.refresh_gate()
    assert hy.ragged_side() == "tpu"
    blocks, hashes = _blocks(n=K * 2 + 1, seed=31)
    blocks[2] = blocks[2][:-1] + b"\x00"
    rok, rpar = CpuCodec(p).scrub_encode_batch(blocks, hashes, True)
    try:
        ok, par = _scrub_by(route, hy, blocks, hashes)
    finally:
        hy.close()
    assert ok.tolist() == rok.tolist() and (par == rpar).all()
    kinds = {e["kind"] for e in hy.obs.events_list()}
    assert ("transport_error" if route == "transport"
            else "sync_failure") in kinds, kinds
    assert hy.obs.bytes_total == {"cpu": sum(map(len, blocks)), "tpu": 0}


# --- CPU encode-schedule cache (satellite) ------------------------------


def test_encode_schedule_cache_bit_identity_and_bound():
    """The encode twin of the decode-schedule cache: partial codewords
    run a cached (k, m, geometry)-keyed sliced schedule, bit-identical
    to the uncached full-width encode; the cache is a bounded LRU."""
    p = _params()
    cpu = CpuCodec(p)
    ref = CpuCodec(p)
    for n in (1, 2, 3, K - 1, K, K + 1, 3 * K - 1, 3 * K, 1, 2):
        blocks, _h = _blocks(n=n, seed=n)
        got = cpu.rs_encode_blocks(blocks)
        want = BlockCodec.rs_encode_blocks(ref, blocks)
        assert got.shape == want.shape and (got == want).all(), n
    keys = list(cpu._enc_cache)
    assert keys and all(kk == (K, M, g) for kk, g in
                        zip(keys, [g for _k1, _m1, g in keys]))
    assert len(cpu._enc_cache) <= CpuCodec._ENC_CACHE_MAX
    # bound enforced under synthetic pressure
    cpu._enc_cache.clear()
    for g in range(1, 200):
        cpu._enc_cache[(K, M, g)] = np.zeros((M, 1), np.uint8)
        while len(cpu._enc_cache) > CpuCodec._ENC_CACHE_MAX:
            cpu._enc_cache.popitem(last=False)
    assert len(cpu._enc_cache) <= CpuCodec._ENC_CACHE_MAX


def test_encode_ragged_schedule_fusion_bit_identity():
    p = _params()
    cpu = CpuCodec(p)
    ref = CpuCodec(p)
    groups = [_blocks(n=n, seed=n)[0]
              for n in (1, K, K + 2, 2, 2 * K, 1)]
    got = cpu.rs_encode_ragged(groups)
    want = BlockCodec.rs_encode_ragged(ref, groups)
    for g, a, b in zip(groups, got, want):
        assert a.shape == b.shape and (a == b).all(), len(g)


# --- metrics ------------------------------------------------------------


def test_transport_metric_families_pass_promlint():
    from garage_tpu.utils.promlint import lint_exposition

    reg = MetricsRegistry()
    tr, dev, cpu = _transport(metrics=reg)
    blocks, hashes = _blocks(n=K)
    it = TransportItem("scrub", (blocks, hashes), len(blocks),
                       sum(map(len, blocks)))
    tr.submit_items("scrub", [it])
    it.future.result(timeout=30)
    body = reg.render()
    for fam in ("transport_staged_bytes_total", "transport_queue_depth",
                "transport_inflight_batches"):
        assert fam in body, fam
    assert lint_exposition(body) == [], lint_exposition(body)
    tr.shutdown()

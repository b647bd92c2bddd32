"""HybridCodec: the CPU floor and the device behind the link gate.

Checks the bytes-level contract: results are bit-identical to the CPU
codec whichever side of the gate ran a batch, the whole batch goes to
the side the gate names, and a broken device never fails or corrupts a
call (the CPU floor runs it).  Runs on the virtual CPU platform —
"device" here is the JAX CPU backend or a scripted fake.  The road the
product runs (feeder → transport) is tests/test_transport.py's.
"""

import hashlib
import threading
import time

import numpy as np

from garage_tpu.ops import make_codec
from garage_tpu.ops.codec import CodecParams
from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.hybrid_codec import HybridCodec
from garage_tpu.utils.data import Hash

K, M = 4, 2


def _params(**kw):
    kw.setdefault("rs_data", K)
    kw.setdefault("rs_parity", M)
    return CodecParams(**kw)


# The gate's verdict on a REAL device codec is a wall-clock rate (16 MiB
# through the JAX CPU backend, under five other test workers): where a
# test asserts the side, the threshold decides it (0 opens on any probe
# that completes, _SHUT holds on any), never the clock.
_OPEN, _SHUT = 0.0, 1e9


def _attached(hy):
    """Wait for make_codec's background device attach."""
    for _ in range(200):
        if hy.tpu is not None:
            break
        time.sleep(0.05)
    assert hy.tpu is not None
    return hy


def _mk_blocks(n, size=2048, seed=0):
    rng = np.random.default_rng(seed)
    blocks = [rng.integers(0, 256, size, dtype=np.uint8).tobytes()
              for _ in range(n)]
    hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
              for b in blocks]
    return blocks, hashes


class _FakeDevice:
    """Scripted device codec (unmetered: no probe hook, so the gate
    treats it as healthy): CPU math with a controllable failure."""

    def __init__(self, params, fail=False):
        self.cpu = CpuCodec(params)
        self.params = params
        self.fail = fail
        self.submitted = 0

    def scrub_encode_batch(self, blocks, hashes, fetch_parity=True):
        self.submitted += 1
        if self.fail:
            raise RuntimeError("injected device failure")
        return self.cpu.scrub_encode_batch(blocks, hashes, fetch_parity)

    def batch_verify(self, blocks, hashes):
        return self.scrub_encode_batch(blocks, hashes, False)[0]


def test_hybrid_matches_cpu_with_corruption():
    blocks, hashes = _mk_blocks(40)
    bad = dict(enumerate(blocks))
    bad[7] = b"\xff" + blocks[7][1:]
    bad[23] = blocks[23][:-1] + b"\x00"
    blocks = [bad[i] for i in range(len(blocks))]
    hy = _attached(make_codec(
        "hybrid", **vars(_params(hybrid_min_link_gibs=_OPEN))))
    cpu = CpuCodec(_params())
    # a bytes-level call is background work with no feeder in front of
    # it: it takes the gate's probe itself ([codec] feeder = false)
    assert hy.ragged_side() == "cpu" and hy.last_gate is None
    ok = hy.batch_verify(blocks, hashes)
    assert hy.ragged_side() == "tpu" and hy.last_gate == "open", hy.info()
    assert hy.obs.bytes_total == {"cpu": 0, "tpu": 40 * 2048}
    assert ok.shape == (40,)
    expect = cpu.batch_verify(blocks, hashes)
    assert np.array_equal(ok, expect)
    assert not ok[7] and not ok[23]
    assert ok.sum() == 38


def _cpu_reference_parity(blocks, k=K, m=M):
    """Whole-batch reference: zero-pad to (ceil(n/k)*k, maxlen), reshape to
    codewords, encode with the CPU codec."""
    cpu = CpuCodec(_params())
    maxlen = max(len(b) for b in blocks)
    pad = (-len(blocks)) % k
    arr = np.zeros((len(blocks) + pad, maxlen), dtype=np.uint8)
    for i, b in enumerate(blocks):
        arr[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    return cpu.rs_encode(arr.reshape(-1, k, maxlen))


def test_hybrid_parity_identical_across_backends():
    # canonical parity must equal the whole-batch CPU reference, including
    # a partial trailing group exercising the device-side shape trim
    blocks, hashes = _mk_blocks(19, size=1000)
    expect = _cpu_reference_parity(blocks)
    for side, min_link in (("cpu", _SHUT), ("tpu", _OPEN)):
        hy = HybridCodec(_params(hybrid_min_link_gibs=min_link))
        ok, parity = hy.scrub_encode_batch(blocks, hashes)
        assert ok.all()
        assert parity.shape == expect.shape
        assert np.array_equal(parity, expect)
        assert hy.ragged_side() == side
        assert hy.obs.bytes_total[side] == 19000, hy.obs.bytes_total
        hy.close()


def test_scrub_encode_batch_contract_tpu_vs_hybrid():
    # the same method on the tpu and hybrid backends must return the same
    # shapes and bits (backend-swap safety), incl. the fetch_parity kwarg
    from garage_tpu.ops.tpu_codec import TpuCodec

    blocks, hashes = _mk_blocks(19, size=768, seed=5)
    tpu = TpuCodec(_params())
    hy = HybridCodec(_params(hybrid_min_link_gibs=_OPEN))
    ok_t, par_t = tpu.scrub_encode_batch(blocks, hashes)
    ok_h, par_h = hy.scrub_encode_batch(blocks, hashes)
    assert np.array_equal(ok_t, ok_h)
    assert par_t.shape == par_h.shape
    assert np.array_equal(par_t, par_h)
    assert np.array_equal(par_t, _cpu_reference_parity(blocks))
    ok_t2, none_t = tpu.scrub_encode_batch(blocks, hashes, fetch_parity=False)
    ok_h2, none_h = hy.scrub_encode_batch(blocks, hashes, fetch_parity=False)
    assert none_t is None and none_h is None
    assert np.array_equal(ok_t2, ok_h2)


def test_hybrid_routes_the_whole_batch_to_the_side_the_gate_names():
    # one routing rule: a healthy device takes the call whole (one
    # submission, every byte on its side), no split with the floor
    p = _params()
    dev = _FakeDevice(p)
    hy = HybridCodec(p, device_codec=dev)
    blocks, hashes = _mk_blocks(37)
    ok, parity = hy.scrub_encode_batch(blocks, hashes)
    assert ok.all()
    assert np.array_equal(parity, _cpu_reference_parity(blocks))
    assert hy.batch_verify(blocks, hashes).all()
    assert dev.submitted == 2
    assert hy.obs.bytes_total == {"cpu": 0, "tpu": 2 * 37 * 2048}


def test_hybrid_absorbs_device_failure():
    # a device that raises costs the caller nothing: the CPU floor's
    # answer, the failure in the event ring, every byte on the cpu side
    # (fails if _routed's fallback is removed)
    p = _params()
    dev = _FakeDevice(p, fail=True)
    hy = HybridCodec(p, device_codec=dev)
    blocks, hashes = _mk_blocks(32)
    blocks[5] = b"\x00" * 2048
    ok, parity = hy.scrub_encode_batch(blocks, hashes)
    assert not ok[5] and ok.sum() == 31
    assert np.array_equal(parity, _cpu_reference_parity(blocks))
    assert np.array_equal(hy.batch_verify(blocks, hashes), ok)
    assert dev.submitted == 2
    assert hy.obs.bytes_total == {"cpu": 2 * 32 * 2048, "tpu": 0}
    fails = [e for e in hy.obs.events_list() if e["kind"] == "sync_failure"]
    assert len(fails) == 2 and fails[0]["reason"] == "RuntimeError"


def test_hybrid_real_device_backend_equivalence():
    # the real TpuCodec as device (JAX CPU platform here): the bytes-
    # level call through its jitted kernels.  make_codec builds the
    # device codec asynchronously (daemon-safe); wait for the attach
    # before asserting it participates.
    blocks, hashes = _mk_blocks(48, size=512, seed=3)
    hy = _attached(make_codec(
        "hybrid", **vars(_params(hybrid_min_link_gibs=_OPEN))))
    ok, parity = hy.scrub_encode_batch(blocks, hashes)
    assert hy.last_gate == "open", hy.info()
    assert ok.all()
    assert np.array_equal(parity, _cpu_reference_parity(blocks))
    assert hy.obs.bytes_total["tpu"] == 48 * 512


def test_hybrid_replication_only_config():
    # rs_data=0 (replication-only, no RS) must construct and verify fine
    p = CodecParams(rs_data=0, rs_parity=0)
    blocks, hashes = _mk_blocks(20)
    for hy in (HybridCodec(p, build_device=False),
               HybridCodec(p, device_codec=_FakeDevice(p))):
        ok = hy.batch_verify(blocks, hashes)
        assert ok.all()
        ok2, parity = hy.scrub_encode_batch(blocks, hashes)
        assert ok2.all() and parity is None
        (ok3, parity3), = hy.scrub_ragged([(blocks, hashes, True)])
        assert ok3.all() and parity3 is None


def test_hybrid_build_device_false_skips_device():
    hy = HybridCodec(_params(), build_device=False)
    assert hy.tpu is None
    blocks, hashes = _mk_blocks(24)
    assert hy.batch_verify(blocks, hashes).all()


def test_hybrid_concurrent_calls_thread_safety():
    # two threads scrubbing through one codec instance must not cross wires
    hy = HybridCodec(_params(hybrid_min_link_gibs=_OPEN))
    blocks_a, hashes_a = _mk_blocks(24, seed=1)
    blocks_b, hashes_b = _mk_blocks(24, seed=2)
    out = {}

    def run(name, b, h):
        out[name] = hy.batch_verify(b, h)

    ts = [threading.Thread(target=run, args=("a", blocks_a, hashes_a)),
          threading.Thread(target=run, args=("b", blocks_b, hashes_b))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert out["a"].all() and out["b"].all()


def test_hybrid_link_gate_cedes_to_cpu_when_probe_below_threshold():
    # With the threshold set impossibly high, the probe holds the gate:
    # the device gets nothing and the call completes correctly on CPU.
    hy = _attached(
        make_codec("hybrid", **vars(_params(hybrid_min_link_gibs=_SHUT))))
    blocks, hashes = _mk_blocks(64, seed=21)
    ok, parity = hy.scrub_encode_batch(blocks, hashes)
    assert hy.last_gate == "hold" and hy.ragged_side() == "cpu"
    assert ok.all()
    assert np.array_equal(parity, _cpu_reference_parity(blocks))
    assert hy.obs.bytes_total == {
        "cpu": sum(len(b) for b in blocks), "tpu": 0}, \
        "the device got work through a gated link"

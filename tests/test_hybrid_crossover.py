"""Hybrid crossover proof (VERDICT r4 #2): the link gate flips where
configured, and results are bit-identical whichever side of the gate a
batch lands.

A real link has one rate, so these tests drive the gate (probe → verdict
→ route) against a synthetic-link device backend whose rate is
configurable (garage_tpu/testing/synthetic_device.py), on both roads:
the CodecFeeder's (→ transport) and the bytes-level call's.
"""

import hashlib

import numpy as np
import pytest

from garage_tpu.ops.codec import CodecParams
from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.feeder import CodecFeeder
from garage_tpu.ops.hybrid_codec import HybridCodec
from garage_tpu.testing.synthetic_device import SyntheticLinkCodec
from garage_tpu.utils.data import Hash

K, M = 4, 2


def _params(**kw):
    kw.setdefault("rs_data", K)
    kw.setdefault("rs_parity", M)
    return CodecParams(**kw)


def _mk_blocks(n, size=2048, seed=0):
    rng = np.random.default_rng(seed)
    blocks = [rng.integers(0, 256, size, dtype=np.uint8).tobytes()
              for _ in range(n)]
    hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
              for b in blocks]
    return blocks, hashes


def test_gate_flips_at_configured_threshold():
    # link below the threshold → gate holds, device gets nothing; link
    # above → gate opens, the device takes every byte.  Same workload,
    # same road (feeder, background scrub), only the measured link rate
    # differs.
    blocks, hashes = _mk_blocks(256, size=4096)
    nbytes = 256 * 4096
    for link, expect_open in ((0.01, False), (5.0, True)):
        p = _params(hybrid_min_link_gibs=0.07)
        dev = SyntheticLinkCodec(p, link_gibs=link, compute_real=True)
        hy = HybridCodec(p, device_codec=dev)
        f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)
        try:
            for _pass in range(3):
                ok, _par = f.submit_scrub(blocks, hashes).result(timeout=60)
                assert ok.all()
        finally:
            f.shutdown()
            hy.close()
        if expect_open:
            assert hy.last_gate == "open"
            assert hy.obs.bytes_total == {"cpu": 0, "tpu": 3 * nbytes}
            assert dev.array_submissions > 0
        else:
            assert hy.last_gate == "hold"
            assert hy.obs.bytes_total == {"cpu": 3 * nbytes, "tpu": 0}
            assert dev.array_submissions == 0
        assert dev.submissions == 0, "the bytes-level path was taken"
        assert hy.last_link_gibs == pytest.approx(link)


def test_crossover_results_bit_identical_through_gate_path():
    # identity mode: real results through probe → gate → device must
    # equal the pure-CPU reference, parity included, on every pass and
    # on both roads (bytes-level call; feeder → transport).
    blocks, hashes = _mk_blocks(96, size=1000, seed=5)
    blocks[10] = b"\x00" * 1000
    p = _params()
    dev = SyntheticLinkCodec(p, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(p, device_codec=dev)
    cpu = CpuCodec(p)
    expect_ok = cpu.batch_verify(blocks, hashes)
    maxlen = max(len(b) for b in blocks)
    arr = np.zeros((len(blocks), maxlen), dtype=np.uint8)
    for i, b in enumerate(blocks):
        arr[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
    expect_par = cpu.rs_encode(arr.reshape(-1, K, maxlen))

    hy.refresh_gate()
    assert hy.last_gate == "open"
    f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)
    try:
        for _pass in range(3):
            ok, parity = hy.scrub_encode_batch(blocks, hashes)
            assert np.array_equal(ok, expect_ok)
            assert np.array_equal(parity, expect_par)
            ok, parity = f.submit_scrub(blocks, hashes).result(timeout=60)
            assert np.array_equal(ok, expect_ok)
            assert np.array_equal(parity, expect_par)
    finally:
        f.shutdown()
        hy.close()
    assert dev.submissions == 3 and dev.array_submissions >= 3
    assert hy.obs.bytes_total == {"cpu": 0, "tpu": 6 * 96 * 1000}

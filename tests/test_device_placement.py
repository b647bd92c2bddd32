"""A staged host buffer must land on the device the codec was built
for, not on the process default: on a TPU host an un-targeted dlpack
adoption keeps a numpy buffer on the host CPU backend, and jit follows
that committed input — every transport dispatch would then run under
XLA:CPU.  Reproduced here on the conftest's virtual CPU devices with a
codec pinned to a non-default one."""

import hashlib

import jax
import numpy as np
import pytest

from garage_tpu.ops.codec import CodecParams
from garage_tpu.ops.tpu_codec import TpuCodec

K, M, COLS = 4, 2, 256


@pytest.fixture(scope="module")
def pinned():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs the virtual multi-device platform")
    codec = TpuCodec(CodecParams(rs_data=K, rs_parity=M),
                     devices=[devs[3]])
    return codec, devs[3]


def _staged(n=8):
    rng = np.random.default_rng(7)
    arr = rng.integers(0, 256, (n, COLS), dtype=np.uint8)
    lengths = np.full((n,), COLS, dtype=np.int32)
    expected = np.stack([
        np.frombuffer(hashlib.blake2s(arr[i].tobytes()).digest(), "<u4")
        for i in range(n)]).astype(np.uint32)
    return arr, lengths, expected


def _devices(tree):
    return [leaf.devices() for leaf in jax.tree_util.tree_leaves(tree)]


def test_codec_holds_its_device(pinned):
    codec, dev = pinned
    assert codec.device == dev
    assert codec._K_enc.devices() == {dev}


def test_hash_submit_lands_on_codec_device(pinned):
    codec, dev = pinned
    arr, lengths, expected = _staged()
    h = codec.hash_submit(arr, lengths)
    assert _devices(h) == [{dev}]
    assert np.array_equal(np.asarray(h), expected)


def test_encode_submit_lands_on_codec_device(pinned):
    codec, dev = pinned
    arr, _, _ = _staged()
    out = codec.encode_submit(arr.reshape(-1, K, COLS))
    assert _devices(out) == [{dev}]
    assert np.array_equal(
        codec.encode_collect(out),
        TpuCodec(CodecParams(rs_data=K, rs_parity=M)).rs_encode(
            arr.reshape(-1, K, COLS)))


def test_scrub_submit_lands_on_codec_device(pinned):
    codec, dev = pinned
    out = codec.scrub_encode_submit(*_staged())
    assert all(d == {dev} for d in _devices(out))
    assert bool(np.all(np.asarray(out[1])))


def test_resident_scrub_lands_on_codec_device(pinned):
    codec, dev = pinned
    arr, lengths, expected = _staged()
    # lanes 0..5 cross the link as misses; 6 and 7 come from the pool
    _, seed = codec.scrub_encode_submit_resident(
        arr, list(range(8)), lengths, expected, [])
    resident = [(r, codec.pool_adopt(seed, r, COLS, 64), COLS)
                for r in (6, 7)]
    assert all(p.devices() == {dev} for _, pages, _ in resident
               for p in pages)
    out, full = codec.scrub_encode_submit_resident(
        arr[:6], list(range(6)), lengths, expected, resident)
    assert all(d == {dev} for d in _devices((out, full)))
    assert bool(np.all(np.asarray(out[1])))
    assert np.array_equal(np.asarray(full), arr)


def test_probe_submit_lands_on_codec_device(pinned):
    codec, dev = pinned
    buf = np.arange(4096, dtype=np.uint8)
    h = codec.probe_submit(buf)
    assert _devices(h) == [{dev}]
    assert codec.probe_collect(h) == int(buf.sum())


def test_backend_tpu_without_a_tpu_raises():
    """`backend = "tpu"` requires the device: with none it raises at
    construction instead of computing on the CPU devices.  (conftest
    hands the other tests the virtual devices; this is the original.)"""
    from conftest import REAL_TPU_DEVICES

    with pytest.raises(RuntimeError, match="found no TPU"):
        REAL_TPU_DEVICES()


def test_shard_mesh_above_the_device_count_raises():
    with pytest.raises(ValueError, match="shard_mesh=4"):
        TpuCodec(CodecParams(rs_data=K, rs_parity=M, shard_mesh=4),
                 devices=jax.devices()[:2])

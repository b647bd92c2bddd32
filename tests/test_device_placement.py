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
PAGE = 512     # a pool page: two of the staged rows


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
    from garage_tpu.ops.device_pool import DevicePool

    codec, dev = pinned
    arr, lengths, expected = _staged()
    pool = DevicePool(codec, pool_bytes=64 * PAGE, page_bytes=PAGE)
    assert pool.array().devices() == {dev}
    # all eight lanes cross the link as misses; 6 and 7 are adopted
    none = pool.row_index(8, COLS, [])
    _, seed = codec.scrub_encode_submit_resident(
        arr, list(range(8)), lengths, expected, pool.array(), none)
    keys = {r: bytes([r]) * 32 for r in (6, 7)}
    assert pool.adopt_lanes(seed, 8, COLS,
                            [(r, keys[r], COLS) for r in (6, 7)]) == (2, 2)
    assert pool.array().devices() == {dev}
    # then 0..5 are misses again and 6, 7 come from the pool
    resident = [(r, pool.lookup(keys[r], COLS).slots) for r in (6, 7)]
    out, full = codec.scrub_encode_submit_resident(
        arr[:6], list(range(6)), lengths, expected, pool.array(),
        pool.row_index(8, COLS, resident))
    assert all(d == {dev} for d in _devices((out, full)))
    assert bool(np.all(np.asarray(out[1])))
    assert np.array_equal(np.asarray(full).view(np.uint8), arr)
    assert pool.read(keys[7]) == arr[7].tobytes()


def test_resident_scrub_on_a_mesh_composes_on_its_first_device():
    """`shard_mesh = 4`: the pool and the composition live on the first
    device, and the composed batch moves onto the mesh for the kernel."""
    from garage_tpu.ops.device_pool import DevicePool

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs the virtual multi-device platform")
    codec = TpuCodec(CodecParams(rs_data=K, rs_parity=M, shard_mesh=4),
                     devices=devs[:4])
    n = 16      # whole codewords on every device
    arr, lengths, expected = _staged(n)
    pool = DevicePool(codec, pool_bytes=64 * PAGE, page_bytes=PAGE)
    _, seed = codec.scrub_encode_submit_resident(
        arr, list(range(n)), lengths, expected, pool.array(),
        pool.row_index(n, COLS, []))
    key = b"k" * 32
    assert pool.adopt_lanes(seed, n, COLS, [(5, key, COLS)]) == (1, 1)
    assert pool.array().devices() == seed.devices() == {devs[0]}
    rows = [r for r in range(n) if r != 5]
    out, full = codec.scrub_encode_submit_resident(
        arr[rows], rows, lengths, expected, pool.array(),
        pool.row_index(n, COLS, [(5, pool.lookup(key, COLS).slots)]))
    assert full.devices() == {devs[0]}
    assert out[1].devices() == set(devs[:4])
    assert bool(np.all(np.asarray(out[1])))
    assert np.array_equal(np.asarray(full).view(np.uint8), arr)


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

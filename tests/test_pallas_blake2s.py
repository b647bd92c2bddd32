"""Pallas BLAKE2s kernel: bit-identity vs hashlib and the XLA scan.

Runs the kernel in Pallas interpret mode on the CPU platform — no TPU
needed for correctness (chip_smoke.py runs the kernel on the chip).
"""

import functools
import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from garage_tpu.ops.pallas_blake2s import blake2s_batch_pallas
from garage_tpu.ops.tpu_blake2s import blake2s_batch


def _random_batch(rng, n, total):
    arr = np.zeros((n, total), np.uint8)
    lengths = np.zeros((n,), np.int32)
    for i in range(n):
        L = int(rng.integers(0, total + 1))
        lengths[i] = L
        arr[i, :L] = rng.integers(0, 256, (L,), np.uint8)
    return arr, lengths


@pytest.mark.parametrize("nchunks", [1, 3, 8])
def test_pallas_blake2s_bit_identical_to_hashlib(nchunks):
    rng = np.random.default_rng(nchunks)
    arr, lengths = _random_batch(rng, 128, nchunks * 64)
    h = np.asarray(blake2s_batch_pallas(
        jnp.asarray(arr), jnp.asarray(lengths), interpret=True))
    for i in range(arr.shape[0]):
        want = hashlib.blake2s(
            arr[i, :lengths[i]].tobytes(), digest_size=32).digest()
        assert h[i].astype("<u4").tobytes() == want, (i, int(lengths[i]))


def test_pallas_blake2s_matches_xla_scan_multi_tile():
    # 256 lanes = two (8, 128) batch tiles through the grid's batch axis
    rng = np.random.default_rng(7)
    arr, lengths = _random_batch(rng, 256, 2 * 64)
    got = np.asarray(blake2s_batch_pallas(
        jnp.asarray(arr), jnp.asarray(lengths), interpret=True))
    want = np.asarray(blake2s_batch(jnp.asarray(arr), jnp.asarray(lengths)))
    assert (got == want).all()


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
def test_words_hash_as_their_bytes(kernel):
    """A batch the device pool composed is uint32 words already (the
    host's view of the staged bytes): both hash kernels take it as it
    is, and the digests are those of the bytes."""
    from garage_tpu.ops.tpu_codec import host_words

    rng = np.random.default_rng(11)
    arr, lengths = _random_batch(rng, 128, 3 * 64)
    fn = (functools.partial(blake2s_batch_pallas, interpret=True)
          if kernel == "pallas" else blake2s_batch)
    of_bytes = np.asarray(fn(jnp.asarray(arr), jnp.asarray(lengths)))
    of_words = np.asarray(fn(jnp.asarray(host_words(arr)),
                             jnp.asarray(lengths)))
    assert (of_words == of_bytes).all()
    assert of_words[5].astype("<u4").tobytes() == hashlib.blake2s(
        arr[5, :lengths[5]].tobytes(), digest_size=32).digest()


def test_pallas_blake2s_empty_and_full_lanes():
    # length-0 lanes must produce the empty-message digest (the scrub
    # path pads batches with such lanes); full lanes exercise the final
    # chunk == last chunk edge
    total = 128
    arr = np.zeros((128, total), np.uint8)
    arr[1] = np.arange(total, dtype=np.uint8)
    lengths = np.zeros((128,), np.int32)
    lengths[1] = total
    h = np.asarray(blake2s_batch_pallas(
        jnp.asarray(arr), jnp.asarray(lengths), interpret=True))
    empty = hashlib.blake2s(b"", digest_size=32).digest()
    assert h[0].astype("<u4").tobytes() == empty
    full = hashlib.blake2s(arr[1].tobytes(), digest_size=32).digest()
    assert h[1].astype("<u4").tobytes() == full

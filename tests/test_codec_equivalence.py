"""CPU ≡ TPU codec differential tests — the invariant SURVEY.md §4 adds for
the BlockCodec seam: both backends bit-identical on hashing, verify, RS
encode and reconstruct (and both identical to hashlib for BLAKE2s)."""

import hashlib
import os

import numpy as np
import pytest

from garage_tpu.ops import make_codec
from garage_tpu.ops.codec import CodecParams


@pytest.fixture(scope="module")
def cpu():
    return make_codec("cpu", rs_data=4, rs_parity=2)


@pytest.fixture(scope="module")
def tpu():
    # runs on the CPU backend of XLA in tests (conftest sets JAX_PLATFORMS=cpu);
    # the computation graph is identical to what runs on a real TPU.
    return make_codec("tpu", rs_data=4, rs_parity=2)


def _blocks(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, dtype=np.uint8).tobytes() for n in sizes]


class TestBlake2s:
    SIZES = [0, 1, 63, 64, 65, 128, 1000, 4096, 16_001]

    def test_jax_blake2s_matches_hashlib(self, tpu):
        blocks = _blocks(self.SIZES)
        got = tpu.batch_hash(blocks)
        want = [hashlib.blake2s(b, digest_size=32).digest() for b in blocks]
        for g, w, n in zip(got, want, self.SIZES):
            assert bytes(g) == w, f"mismatch at size {n}"

    def test_rolled_vs_unrolled_compress(self):
        """The TPU path unrolls all 10 rounds; CPU uses a rolled scan.
        Both must be bit-identical.  Runs EAGERLY (un-jitted): XLA-CPU
        compile of the unrolled body hangs under the forced-8-device test
        platform; op-by-op eager avoids the compile entirely."""
        import jax.numpy as jnp

        from garage_tpu.ops.tpu_blake2s import compress, compress_rolled

        rng = np.random.default_rng(7)
        h = jnp.asarray(rng.integers(0, 2**32, (8, 4), dtype=np.uint32))
        m = jnp.asarray(rng.integers(0, 2**32, (16, 4), dtype=np.uint32))
        t = jnp.asarray(np.array([64, 65, 128, 1], dtype=np.uint32))
        f = jnp.asarray(np.array([False, True, False, True]))
        a = np.asarray(compress(h, m, t, f))
        b = np.asarray(compress_rolled(h, m, t, f))
        assert np.array_equal(a, b)

    def test_cpu_tpu_hash_identical(self, cpu, tpu):
        blocks = _blocks([777, 1024, 8192], seed=1)
        assert [bytes(h) for h in cpu.batch_hash(blocks)] == [
            bytes(h) for h in tpu.batch_hash(blocks)
        ]

    def test_batch_verify(self, cpu, tpu):
        blocks = _blocks([4096, 4096, 4096], seed=2)
        hashes = cpu.batch_hash(blocks)
        # corrupt middle block
        bad = bytearray(blocks[1])
        bad[100] ^= 0xFF
        blocks[1] = bytes(bad)
        for codec in (cpu, tpu):
            ok = codec.batch_verify(blocks, hashes)
            assert ok.tolist() == [True, False, True]


class TestReedSolomon:
    def test_cpu_tpu_encode_identical(self, cpu, tpu):
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, (6, 4, 512), dtype=np.uint8)
        assert np.array_equal(cpu.rs_encode(data), tpu.rs_encode(data))

    def test_reconstruct_roundtrip_both_backends(self, cpu, tpu):
        rng = np.random.default_rng(4)
        data = rng.integers(0, 256, (3, 4, 256), dtype=np.uint8)
        for codec in (cpu, tpu):
            parity = codec.rs_encode(data)
            code = np.concatenate([data, parity], axis=1)  # (3, 6, 256)
            present = [1, 3, 4, 5]  # lost shards 0 and 2
            rec = codec.rs_reconstruct(code[:, present, :], present)
            assert np.array_equal(rec, data)

    def test_shard_unshard(self, cpu):
        block = os.urandom(1_000_003)  # not a multiple of k
        shards, n = cpu.shard_block(block)
        assert shards.shape[0] == 4
        assert cpu.unshard_block(shards, n) == block

    def test_end_to_end_block_repair(self, cpu, tpu):
        """Full block → shard → encode → lose shards → reconstruct → verify."""
        block = os.urandom(16 * 1024)
        h = bytes(cpu.batch_hash([block])[0])
        shards, n = cpu.shard_block(block)
        parity = tpu.rs_encode(shards[None])[0]
        code = np.concatenate([shards, parity], axis=0)
        present = [0, 2, 4, 5]
        rec = tpu.rs_reconstruct(code[None][:, present, :], present)[0]
        restored = cpu.unshard_block(rec, n)
        assert restored == block
        assert bytes(tpu.batch_hash([restored])[0]) == h


class TestCompression:
    def test_roundtrip_and_incompressible(self, cpu):
        compressible = b"garage" * 10000
        c = cpu.compress(compressible)
        assert c is not None and len(c) < len(compressible)
        assert cpu.decompress(c) == compressible
        assert cpu.compress(os.urandom(4096)) is None  # not smaller → None

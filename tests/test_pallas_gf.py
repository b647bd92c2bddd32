"""Bit-identity of the Pallas GF(2^8) kernel against the numpy oracle.

Runs the kernel through the Pallas INTERPRETER (no TPU needed), so what
is verified is the kernel's math, not Mosaic codegen (chip_smoke.py
runs the kernel on the chip)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from garage_tpu.ops import gf256  # noqa: E402
from garage_tpu.ops.pallas_gf import PallasGf, reference_apply  # noqa: E402


@pytest.mark.parametrize("k,m", [(4, 2), (8, 4)])
def test_encode_matrix_bit_identity(k, m):
    rng = np.random.default_rng(k * 10 + m)
    mat = gf256.rs_parity_matrix(k, m)
    pg = PallasGf(mat, tile=128, interpret=True)
    sh = rng.integers(0, 2**32, (2, k, 300), dtype=np.uint32)
    out = np.asarray(pg(jnp.asarray(sh)))
    assert (out == reference_apply(mat, sh)).all()


def test_decode_matrix_and_row_restriction():
    rng = np.random.default_rng(7)
    dec = gf256.rs_decode_matrix(8, 4, [0, 1, 3, 4, 6, 7, 8, 9])
    pg = PallasGf(dec, tile=128, interpret=True)
    sh = rng.integers(0, 2**32, (1, 8, 257), dtype=np.uint32)
    assert (np.asarray(pg(jnp.asarray(sh)))
            == reference_apply(dec, sh)).all()
    rows = np.ascontiguousarray(dec[[2, 5]])
    pgr = PallasGf(rows, tile=128, interpret=True)
    assert (np.asarray(pgr(jnp.asarray(sh)))
            == reference_apply(rows, sh)).all()


def test_tile_padding_and_batch_fold():
    """Columns not divisible by the tile and multi-codeword batches."""
    rng = np.random.default_rng(3)
    mat = gf256.rs_parity_matrix(4, 2)
    pg = PallasGf(mat, tile=256, interpret=True)
    for b, s4 in [(1, 100), (3, 511), (5, 256)]:
        sh = rng.integers(0, 2**32, (b, 4, s4), dtype=np.uint32)
        assert (np.asarray(pg(jnp.asarray(sh)))
                == reference_apply(mat, sh)).all(), (b, s4)


def test_wide_shards_batched_no_transpose_path():
    """s4 >= 2048 takes the batched in-place codeword walk (no fold
    transpose) — must be bit-identical to the reference, including
    column padding and multiple codewords."""
    rng = np.random.default_rng(4)
    mat = gf256.rs_parity_matrix(4, 2)
    pg = PallasGf(mat, tile=1024, interpret=True)
    for b, s4 in [(1, 2048), (3, 2500)]:
        sh = rng.integers(0, 2**32, (b, 4, s4), dtype=np.uint32)
        assert (np.asarray(pg(jnp.asarray(sh)))
                == reference_apply(mat, sh)).all(), (b, s4)


def test_pallas_latch_permanent_vs_transient(monkeypatch):
    """VERDICT r3 #8: one transient backend error must NOT permanently
    demote the Pallas kernel; a Mosaic-unsupported error must."""
    from garage_tpu.ops.codec import CodecParams
    from garage_tpu.ops.tpu_codec import (
        PALLAS_MAX_TRANSIENT_FAILS,
        TpuCodec,
    )

    codec = TpuCodec(CodecParams(rs_data=4, rs_parity=2))
    rng = np.random.default_rng(0)
    flat = rng.integers(0, 256, (1, 4, 64), dtype=np.uint8)

    class Boom:
        def __init__(self, exc):
            self.exc = exc
            self.calls = 0

        def __call__(self, u32):
            self.calls += 1
            raise self.exc

    # transient error (device flake): retried, not latched
    boom = Boom(RuntimeError("UNAVAILABLE: connection reset by peer"))
    monkeypatch.setattr(codec, "_pallas_for", lambda mat: boom)
    out1 = codec._gf_apply_np(flat, codec._K_enc, mat=codec._enc_mat)
    assert codec._pallas_ok, "transient error must not latch pallas off"
    assert codec._pallas_transient_fails == 1
    # the XLA fallback still produced the right answer
    from garage_tpu.ops.cpu_codec import CpuCodec

    ref = CpuCodec(CodecParams(rs_data=4, rs_parity=2))
    exp = ref.rs_encode(flat)
    assert (out1 == exp).all()

    # enough consecutive transient failures eventually demote
    for _ in range(PALLAS_MAX_TRANSIENT_FAILS):
        codec._gf_apply_np(flat, codec._K_enc, mat=codec._enc_mat)
    assert not codec._pallas_ok

    # a success in between resets the counter
    codec2 = TpuCodec(CodecParams(rs_data=4, rs_parity=2))
    codec2._pallas_transient_fails = PALLAS_MAX_TRANSIENT_FAILS - 1
    # interpret-mode PallasGf works on CPU → success path resets counter
    out = codec2.rs_encode(flat)
    assert (out == exp).all()

    # permanent error latches immediately
    codec3 = TpuCodec(CodecParams(rs_data=4, rs_parity=2))
    boom3 = Boom(RuntimeError("Mosaic lowering is not supported here"))
    monkeypatch.setattr(codec3, "_pallas_for", lambda mat: boom3)
    codec3._gf_apply_np(flat, codec3._K_enc, mat=codec3._enc_mat)
    assert not codec3._pallas_ok
    assert boom3.calls == 1

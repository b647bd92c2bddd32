"""Pallas TPU kernel for batched BLAKE2s-256 — the scrub hash hot loop.

Why a hand kernel.  The XLA formulation (ops/tpu_blake2s.py) is a
lax.scan over 64-byte chunks whose carried state is an (8, B) array in
HBM: every scan step re-loads and re-stores the 16 state words plus the
chunk's message words through HBM, and XLA materializes the masked
select (`jnp.where(active, h_new, h)`) as another full state round-trip.
Measured on v5e the scan runs at 0.18 / 1.5 / 3.8 GiB/s at 16 / 256 /
1024 lanes — far below the VPU's arithmetic ceiling for the ~25
uint32 ops/byte BLAKE2s costs, i.e. the scan is bound by per-step state
traffic, not by hashing arithmetic.  This kernel keeps the 8 state words
resident in VMEM scratch across ALL chunks of a batch tile; the only
per-chunk HBM traffic is the 64 message bytes per lane, streamed by the
Pallas pipeline (double-buffered DMA overlapping compute).

Layout.  One VPU lane per message (the reference hashes blocks one at a
time on CPU — ref src/block/repair.rs:438-490, src/util/data.rs:117; the
TPU axis of parallelism is across blocks).  The batch is shaped
(R, 128) = (sublane-rows, lanes) so every one of the 16 working-state
values is a native (R, 128) uint32 vreg tile at R = 8.  The host-side
wrapper transposes the padded messages once to (C, 16, R, 128) word
layout — a single HBM-bandwidth pass that replaces the scan's per-step
gathers.

Grid = (batch_tiles, C): the chunk axis is innermost and sequential
("arbitrary" semantics), so the VMEM scratch state legally carries
between steps; h initializes at chunk 0 and flushes to the output block
at chunk C-1 (the output block index is constant along the chunk axis,
so Mosaic copies it out exactly once per batch tile).

Exactly RFC 7693 (sequential mode, digest 32 B, no key), bit-identical
to hashlib.blake2s and to ops/tpu_blake2s.blake2s_batch — asserted in
tests/test_pallas_blake2s.py (interpret mode, no TPU needed).
Variable-length lanes: per-lane byte counts give each lane its own final
chunk (t counter capped at the true length, finalization flag on the
lane's last chunk, state frozen after it) — identical masking semantics
to blake2s_batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .tpu_blake2s import H0, IV, SIGMA, _G_IDX, bytes_to_words

LANE = 128


def lanes_supported(nlanes: int) -> bool:
    """Batch widths the kernel tiles: whole 128-lane rows, and either at
    most 8 rows (one tile) or a multiple of 8 rows.  1536 lanes = 12
    rows has neither, and the chip's compiler refuses its (6, 128)
    tiles."""
    rows, rem = divmod(nlanes, LANE)
    return rem == 0 and rows > 0 and (rows <= 8 or rows % 8 == 0)


def _rotr(x, n: int):
    return (x >> jnp.uint32(n)) | (x << jnp.uint32(32 - n))


def _kernel(nchunks: int, msg_ref, len_ref, o_ref, h_ref):
    """One grid step = one 64-byte chunk for one (R, 128)-lane tile.

    msg_ref (1, 16, R, 128) u32 — this chunk's message words;
    len_ref (R, 128) u32 — true byte lengths; o_ref (8, R, 128) u32 —
    digests, written at the final chunk; h_ref (8, R, 128) u32 VMEM
    scratch — the carried state.
    """
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for w in range(8):
            h_ref[w] = jnp.full(len_ref.shape, H0[w], jnp.uint32)

    lengths = len_ref[...]
    # index of each lane's final chunk: ceil(L/64)-1, clamped >= 0.
    # min/max spelled as where-selects: this backend's Mosaic fails to
    # legalize vector arith.maxui/minui on uint32.
    nch = (lengths + jnp.uint32(63)) // jnp.uint32(64)
    last = jnp.where(nch == 0, jnp.uint32(0), nch - jnp.uint32(1))
    cj = j.astype(jnp.uint32)
    tend = (cj + jnp.uint32(1)) * jnp.uint32(64)
    t = jnp.where(tend < lengths, tend, lengths)
    f = cj == last

    h = [h_ref[w] for w in range(8)]
    m = [msg_ref[0, w] for w in range(16)]
    v = list(h) + [
        jnp.full(lengths.shape, IV[0], jnp.uint32),
        jnp.full(lengths.shape, IV[1], jnp.uint32),
        jnp.full(lengths.shape, IV[2], jnp.uint32),
        jnp.full(lengths.shape, IV[3], jnp.uint32),
        jnp.uint32(IV[4]) ^ t,
        jnp.full(lengths.shape, IV[5], jnp.uint32),
        jnp.uint32(IV[6]) ^ jnp.where(f, jnp.uint32(0xFFFFFFFF),
                                      jnp.uint32(0)),
        jnp.full(lengths.shape, IV[7], jnp.uint32),
    ]
    for r in range(10):
        s = SIGMA[r]
        for g, (ia, ib, ic, id_) in enumerate(_G_IDX):
            x, y = m[s[2 * g]], m[s[2 * g + 1]]
            a, b, c, d = v[ia], v[ib], v[ic], v[id_]
            a = a + b + x
            d = _rotr(d ^ a, 16)
            c = c + d
            b = _rotr(b ^ c, 12)
            a = a + b + y
            d = _rotr(d ^ a, 8)
            c = c + d
            b = _rotr(b ^ c, 7)
            v[ia], v[ib], v[ic], v[id_] = a, b, c, d
    # lanes whose message already ended stop updating state
    active = cj <= last
    for w in range(8):
        h_ref[w] = jnp.where(active, h[w] ^ v[w] ^ v[w + 8], h[w])

    @pl.when(j == nchunks - 1)
    def _flush():
        for w in range(8):
            o_ref[w] = h_ref[w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def blake2s_words_pallas(msg, lengths, interpret: bool = False):
    """msg (C, 16, R, 128) uint32 chunk-major message words; lengths
    (R, 128) uint32 → (8, R, 128) uint32 digests."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nchunks, _, rows, _ = msg.shape
    # batch tile: up to 8 sublane-rows (1024 lanes) per grid step — one
    # native (8, 128) vreg per state word; bigger tiles spill.  Mosaic
    # wants the tile's row count to be the whole dim or a multiple of 8
    # (lanes_supported is the caller-side guard).
    assert lanes_supported(rows * LANE), rows
    rt = min(rows, 8)
    grid = (rows // rt, nchunks)
    return pl.pallas_call(
        functools.partial(_kernel, nchunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 16, rt, LANE), lambda i, j: (j, 0, i, 0)),
            pl.BlockSpec((rt, LANE), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, rt, LANE), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((8, rows, LANE), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((8, rt, LANE), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(msg, lengths)


def blake2s_batch_pallas(data_u8: jax.Array, lengths: jax.Array,
                         interpret: bool = False) -> jax.Array:
    """Drop-in for tpu_blake2s.blake2s_batch on lane counts divisible by
    128: data_u8 (B, C*64) uint8 zero-padded messages (or their (B, C*16)
    uint32 words), lengths (B,) true byte counts → (B, 8) uint32 digests
    (little-endian word order).

    Jittable; the (B, C, 16) → (C, 16, B/128, 128) word transpose runs
    as one XLA HBM pass feeding the kernel's streaming layout.
    """
    words = bytes_to_words(data_u8)
    bsz, total = words.shape
    assert total % 16 == 0 and total > 0
    assert lanes_supported(bsz), bsz
    nchunks = total // 16
    rows = bsz // LANE
    msg = jnp.transpose(
        words.reshape(bsz, nchunks, 16), (1, 2, 0)
    ).reshape(nchunks, 16, rows, LANE)
    lanes = lengths.astype(jnp.uint32).reshape(rows, LANE)
    h = blake2s_words_pallas(msg, lanes, interpret=interpret)
    return h.reshape(8, bsz).T

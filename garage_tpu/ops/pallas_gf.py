"""Pallas TPU kernel for the GF(2^8) mask-XOR matrix apply.

The north star (BASELINE.json) asks for the Reed-Solomon hot op as a
hand-written TPU kernel rather than XLA-fused jnp.  The XLA formulation
(ops/tpu_codec.py gf_apply) materializes k×8 broadcast masks per output
row across the whole batch in HBM before the XOR-reduce, so the op is
HBM-bound far below the VPU's rate.  This kernel keeps one (k, TILE)
uint32 block of the codeword resident in VMEM and produces all r output
rows from it — each input byte is read from HBM exactly once, all the
mask/select/XOR traffic happens at VMEM bandwidth.

Math (identical to gf_apply, bit-for-bit):
  gfmul(c, x) = XOR_b bit_b(x) · gfmul(c, 2^b)          (GF(2)-linearity)
applied bytewise inside uint32 lanes: m1 = (x >> b) & 0x01010101 puts
bit b of every byte in that byte's low bit; multiplying by the scalar
constant d = gfmul(c_pj, 2^b) (< 256) then yields d in every byte whose
bit was set, with no cross-byte carries (d·1 < 256) — one shift+and per
(input row, bit) and one mul+xor per output row.

The kernel is validated bit-identically against the numpy/XLA versions
in tests (interpret mode — no TPU needed for correctness).

Tuned on a v5e in round 5 (2026-07-31, under jax 0.4.37 — not
re-measured on the current stack).
Lessons that produced the current form:
  - loop order: materializing all k*8 bit-plane masks before the output
    loop (the original kernel) is a 64-vector live range that spills —
    ~23 GiB/s at the old tile=512 default;
  - tile size: 8192 u32 columns (12 rows × 32 KiB ≈ 384 KiB/step in
    VMEM) beats both 512 (grid overhead) and ≥32k (VMEM pressure);
  - mask algebra: m1 * d (scalar constant) beats mask-expand-then-AND
    (((x>>b)&one)*0xFF) & K — one fewer vector op per term.
Result then: the hand kernel beat the XLA mask-XOR formulation on the
same resident data in every paired run (109.5 vs 79.2 GiB/s), timed with in-dispatch fori_loop reps differenced at two
rep counts and a device→host fetch of a scalar checksum as the sync
point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256

LANE = 128          # TPU lane width
SUBLANES = 8        # uint32 tile: (8, 128)


def _kernel(k: int, r: int, x_ref, consts_ref, o_ref):
    """One grid step: x_ref (k, T) uint32 codeword slab in VMEM,
    consts_ref (r, k, 8) uint32 SCALAR gf constants (< 256), o_ref
    (r, T) uint32.

    Loop order matters: each bit-plane m1 is computed once and consumed
    by all r accumulators immediately, so only r+1 T-length vectors are
    live at any point.  (Materializing all k*8 masks before the output
    loop spills out of vector registers and runs ~6x slower on v5e.)
    m1 has bytes in {0,1}; multiplying by a byte constant d < 256 yields
    d in every set byte with no cross-byte carries."""
    one = jnp.uint32(0x01010101)
    accs = [jnp.zeros_like(x_ref[0, ...]) for _ in range(r)]
    for i in range(k):
        xi = x_ref[i, ...]
        for b in range(8):
            m1 = (xi >> jnp.uint32(b)) & one
            for p in range(r):
                accs[p] = accs[p] ^ (m1 * consts_ref[p, i, b])
    for p in range(r):
        o_ref[p, ...] = accs[p]


def gf_scalar_consts(mat: np.ndarray) -> np.ndarray:
    """GF(2^8) matrix (r, k) → (r, k, 8) uint32 plain scalar constants
    gfmul(mat[p,i], 2^b) for the multiply-form kernel (contrast
    tpu_codec.gf_mask_consts, whose constants are byte-replicated for
    the mask-AND form used by the XLA path)."""
    r, k = mat.shape
    K = np.zeros((r, k, 8), np.uint32)
    for p in range(r):
        for i in range(k):
            for b in range(8):
                K[p, i, b] = gf256.gf_mul(int(mat[p, i]), 1 << b)
    return K


@functools.partial(jax.jit, static_argnames=("k", "r", "tile", "interpret"))
def _apply_flat(x, consts, k: int, r: int, tile: int, interpret: bool):
    from jax.experimental import pallas as pl

    n = x.shape[-1]
    grid = (n // tile,)
    return pl.pallas_call(
        functools.partial(_kernel, k, r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, tile), lambda j: (0, j)),
            pl.BlockSpec((r, k, 8), lambda j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.uint32),
        interpret=interpret,
    )(x, consts)


def _kernel_batched(k: int, r: int, x_ref, consts_ref, o_ref):
    """Batched variant of _kernel: blocks carry a leading size-1
    codeword axis so the grid walks codewords DIRECTLY in the (B, k,
    S4) layout — a codeword's k rows are contiguous there, eliminating
    the fold-into-columns transpose of the flat path (which cost a full
    HBM round-trip of the batch on the fused scrub path)."""
    one = jnp.uint32(0x01010101)
    accs = [jnp.zeros_like(x_ref[0, 0, ...]) for _ in range(r)]
    for i in range(k):
        xi = x_ref[0, i, ...]
        for b in range(8):
            m1 = (xi >> jnp.uint32(b)) & one
            for p in range(r):
                accs[p] = accs[p] ^ (m1 * consts_ref[p, i, b])
    for p in range(r):
        o_ref[0, p, ...] = accs[p]


@functools.partial(jax.jit, static_argnames=("k", "r", "tile", "interpret"))
def _apply_batched(x, consts, k: int, r: int, tile: int, interpret: bool):
    from jax.experimental import pallas as pl

    b, _k, s4 = x.shape
    grid = (b, s4 // tile)
    return pl.pallas_call(
        functools.partial(_kernel_batched, k, r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k, tile), lambda c, j: (c, 0, j)),
            pl.BlockSpec((r, k, 8), lambda c, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, tile), lambda c, j: (c, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, r, s4), jnp.uint32),
        interpret=interpret,
    )(x, consts)


class PallasGf:
    """Callable (B, k, S4) uint32 → (B, r, S4) uint32, same contract as
    tpu_codec.gf_apply, but one VMEM-resident Pallas dispatch per
    codeword slab.  `interpret=True` runs the kernel in the Pallas
    interpreter (any backend — used for CPU-side bit-identity tests)."""

    def __init__(self, mat: np.ndarray, tile: int = 8192,
                 interpret: bool = False):
        self.r, self.k = mat.shape
        self.tile = tile
        self.interpret = interpret
        self.consts = jnp.asarray(gf_scalar_consts(mat))

    def __call__(self, shards_u32: jax.Array) -> jax.Array:
        b, k, s4 = shards_u32.shape
        assert k == self.k, (k, self.k)
        # clamp the tile for small shards: padding a 1 KiB shard to the
        # 8192-column production tile would multiply the work 8-64x; a
        # power-of-two tile ≥ s4 keeps padding ≤ 2x (one jit variant per
        # clamped size — O(log) shapes)
        tile = 512
        while tile < min(self.tile, s4):
            tile <<= 1
        tile = min(tile, self.tile)
        pad = (-s4) % tile
        if pad:
            shards_u32 = jnp.pad(shards_u32, ((0, 0), (0, 0), (0, pad)))
        if s4 + pad >= 2048:
            # wide shards (the scrub/batch path): walk codewords in
            # place — their k rows are contiguous in (B, k, S4), so no
            # transpose touches HBM (the fold path's swapaxes cost a
            # full round-trip of the batch)
            out = _apply_batched(shards_u32, self.consts, self.k,
                                 self.r, tile, self.interpret)
            return out[..., :s4]
        # narrow shards: fold the batch into the column axis so tiles
        # stay full; codewords are independent, and tile-aligned
        # concatenation keeps each grid step inside one codeword
        x = jnp.swapaxes(shards_u32, 0, 1).reshape(self.k, -1)
        out = _apply_flat(x, self.consts, self.k, self.r, tile,
                          self.interpret)
        out = jnp.swapaxes(out.reshape(self.r, b, -1), 0, 1)
        return out[..., :s4]


def reference_apply(mat: np.ndarray, shards_u32: np.ndarray) -> np.ndarray:
    """numpy oracle in the uint32 domain (via the byte-domain gf256
    reference)."""
    b, k, s4 = shards_u32.shape
    as_bytes = shards_u32.view("<u4").astype("<u4").tobytes()
    arr = np.frombuffer(as_bytes, dtype=np.uint8).reshape(b, k, s4 * 4)
    out = gf256.gf_matmul_blocks(mat, arr)
    return np.frombuffer(out.tobytes(), dtype="<u4").reshape(
        b, mat.shape[0], s4)

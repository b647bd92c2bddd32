"""BLAKE2s-256 on TPU as a batched JAX computation.

The reference verifies block integrity with a sequential per-block blake2
hash on CPU (ref src/block/block.rs:66-78, src/util/data.rs:117).  BLAKE2 is
inherently sequential *within* a block (each 64-byte chunk's compression
feeds the next), so the TPU axis of parallelism is *across* blocks.

Layout is LANE-MAJOR: every one of the 16 state words is a (B,) uint32
vector with the batch on the minor (128-lane) dimension, and the 10 rounds
× 8 G quarter-rounds are fully unrolled with the SIGMA message schedule
resolved at trace time — zero gathers, zero rolls, pure uint32
add/xor/shift VPU ops.  (The first version kept state as (B, 4) row
vectors: minor dim 4 wastes 124 of 128 VPU lanes and the per-round SIGMA
gathers dominate; lane-major is ~an order of magnitude faster.)

All arithmetic is uint32 — native VPU ops; this is why the framework's
default block hash is BLAKE2s (32-bit) rather than the reference's blake2b
(64-bit, which TPUs emulate slowly).

Exactly RFC 7693 (sequential mode, digest 32 B, no key); verified
bit-identical to hashlib.blake2s in tests/test_codec_equivalence.py.
Variable-length lanes supported via per-lane byte lengths: lanes whose
message ended stop updating state (masked select), and the final-chunk flag
and byte counter are computed per lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IV = np.array([
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
], dtype=np.uint32)

SIGMA = np.array([
    [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15],
    [14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3],
    [11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4],
    [7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8],
    [9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13],
    [2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9],
    [12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11],
    [13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10],
    [6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5],
    [10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0],
], dtype=np.int32)

# the 8 G applications per round: (state indices a,b,c,d)
_G_IDX = [
    (0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),
    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14),
]

# h[0] ^= 0x01010000 ^ digest_len  (param block: fanout=1, depth=1, len=32)
H0 = IV.copy()
H0[0] ^= 0x01010020


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def compress(h: jax.Array, m: jax.Array, t: jax.Array, f: jax.Array) -> jax.Array:
    """One BLAKE2s compression, lane-major.

    h (8, B) uint32 state; m (16, B) uint32 message words (LE);
    t (B,) uint32 low byte counter (messages < 4 GiB so t_hi = 0);
    f (B,) bool final-chunk flag.  Returns the new (8, B) state.
    """
    hw = [h[i] for i in range(8)]
    mw = [m[i] for i in range(16)]
    iv = [jnp.uint32(x) for x in IV]
    v = hw + [
        jnp.broadcast_to(iv[0], t.shape),
        jnp.broadcast_to(iv[1], t.shape),
        jnp.broadcast_to(iv[2], t.shape),
        jnp.broadcast_to(iv[3], t.shape),
        iv[4] ^ t,
        jnp.broadcast_to(iv[5], t.shape),
        iv[6] ^ jnp.where(f, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)),
        jnp.broadcast_to(iv[7], t.shape),
    ]
    for r in range(10):
        s = SIGMA[r]
        for g, (ia, ib, ic, id_) in enumerate(_G_IDX):
            x, y = mw[s[2 * g]], mw[s[2 * g + 1]]
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
    return jnp.stack([hw[i] ^ v[i] ^ v[i + 8] for i in range(8)])


def compress_rolled(h: jax.Array, m: jax.Array, t: jax.Array, f: jax.Array) -> jax.Array:
    """Same compression with the 10 rounds as a lax.scan — ~10× smaller
    compiled body.  Used on CPU (tests, 1-core CI boxes) where XLA compile
    time of the fully unrolled body is prohibitive; bit-identical to
    `compress` (asserted in tests/test_codec_equivalence.py)."""
    iv = jnp.asarray(IV)
    bsz = t.shape[0]
    v = jnp.concatenate([
        h,
        jnp.broadcast_to(iv[0:4, None], (4, bsz)),
        jnp.stack([
            iv[4] ^ t,
            jnp.broadcast_to(iv[5], t.shape),
            iv[6] ^ jnp.where(f, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)),
            jnp.broadcast_to(iv[7], t.shape),
        ]),
    ])
    sigma = jnp.asarray(SIGMA)

    def round_body(v, s):
        mp = jnp.take(m, s, axis=0)  # (16, B) message words in round order
        vw = [v[i] for i in range(16)]
        for g, (ia, ib, ic, id_) in enumerate(_G_IDX):
            x, y = mp[2 * g], mp[2 * g + 1]
            a, b, c, d = vw[ia], vw[ib], vw[ic], vw[id_]
            a = a + b + x
            d = _rotr(d ^ a, 16)
            c = c + d
            b = _rotr(b ^ c, 12)
            a = a + b + y
            d = _rotr(d ^ a, 8)
            c = c + d
            b = _rotr(b ^ c, 7)
            vw[ia], vw[ib], vw[ic], vw[id_] = a, b, c, d
        return jnp.stack(vw), None

    v, _ = jax.lax.scan(round_body, v, sigma)
    return h ^ v[0:8] ^ v[8:16]


def bytes_to_words(data_u8: jax.Array) -> jax.Array:
    """uint8 (..., 4n) → uint32 (..., n), little-endian.  Words stay
    what they are: a batch the device pool composed is uint32 already
    (ops/device_pool.py), viewed on the host before it crossed the link.

    Uses bitcast_convert_type (a relayout, no arithmetic): measured 33
    vs 24 GiB/s for the arithmetic shift/or formulation on v5e, and the
    byte-pack feeds every hash/GF dispatch so it is on the hot path.
    Byte order is the platform's; TPU and x86 are both little-endian,
    asserted against the arithmetic form in
    tests/test_codec_equivalence.py (a hypothetical BE platform would
    flip this flag)."""
    if data_u8.dtype == jnp.uint32:
        return data_u8
    if _BITCAST_PACK:
        return jax.lax.bitcast_convert_type(
            data_u8.reshape(data_u8.shape[:-1] + (-1, 4)), jnp.uint32)
    b = data_u8.astype(jnp.uint32).reshape(data_u8.shape[:-1] + (-1, 4))
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


_BITCAST_PACK = True


# Process-wide override for the unroll choice (None = auto by backend).
# tests/test_chip_compile.py sets it to True: compiling for a described
# chip, default_backend() is still "cpu" and would pick the rolled body.
_UNROLL_OVERRIDE: bool | None = None


def set_unroll_override(value: bool | None) -> None:
    global _UNROLL_OVERRIDE
    _UNROLL_OVERRIDE = value


def _default_unroll() -> bool:
    """Full round unroll on TPU (no gathers, fastest); rolled rounds on
    CPU, where the ~1100-primitive unrolled scan body makes XLA's 1-core
    compile pathologically slow."""
    if _UNROLL_OVERRIDE is not None:
        return _UNROLL_OVERRIDE
    return jax.default_backend() != "cpu"


def blake2s_batch(
    data_u8: jax.Array, lengths: jax.Array, unroll: bool = None
) -> jax.Array:
    """Hash B zero-padded messages.

    data_u8 (B, C*64) uint8 — messages padded with zeros to a common
    multiple-of-64 length (C ≥ 1 chunks) — or the same as (B, C*16)
    uint32 words; lengths (B,) int32 true byte counts.  Returns (B, 8)
    uint32 digests (little-endian word order).
    """
    if unroll is None:
        unroll = _default_unroll()
    compress_fn = compress if unroll else compress_rolled
    words = bytes_to_words(data_u8)
    bsz, total = words.shape
    assert total % 16 == 0 and total > 0
    nchunks = total // 16
    # (B, C, 16) → (C, 16, B): batch lane-major for the scan body
    msg = jnp.transpose(words.reshape(bsz, nchunks, 16), (1, 2, 0))
    lengths = lengths.astype(jnp.uint32)
    # index of each lane's final chunk: ceil(L/64)-1, clamped ≥ 0
    last = jnp.maximum(
        (lengths + jnp.uint32(63)) // jnp.uint32(64), jnp.uint32(1)
    ) - jnp.uint32(1)
    h0 = jnp.broadcast_to(jnp.asarray(H0)[:, None], (8, bsz))

    def step(h, xs):
        c, m = xs
        c32 = c.astype(jnp.uint32)
        t = jnp.minimum((c32 + 1) * jnp.uint32(64), lengths)
        f = c32 == last
        h_new = compress_fn(h, m, t, f)
        active = c32 <= last
        return jnp.where(active[None, :], h_new, h), None

    # the scope names the scan's `while` in a trace, whichever jitted
    # program it was lowered into
    with jax.named_scope("blake2s_scan"):
        h, _ = jax.lax.scan(
            step, h0, (jnp.arange(nchunks, dtype=jnp.int32), msg)
        )
    return h.T


@functools.partial(jax.jit, static_argnames=())
def blake2s_batch_jit(data_u8: jax.Array, lengths: jax.Array) -> jax.Array:
    return blake2s_batch(data_u8, lengths)


def digests_to_bytes(h: np.ndarray) -> list:
    """(B, 8) uint32 → list of 32-byte digests."""
    le = np.asarray(h, dtype="<u4")
    return [le[i].tobytes() for i in range(le.shape[0])]

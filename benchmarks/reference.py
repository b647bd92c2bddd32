"""The plain references: the same semantics, straightforwardly, and
nothing of the program.  hashlib BLAKE2s for block ids, GF(2^8)
Reed-Solomon parity by table look-up in numpy.

The published parts they follow: a block's id is BLAKE2s-256 of its
bytes (Garage, `src/util/data.rs` blake2sum); RS(k,m) is systematic
over GF(2^8) with the polynomial 0x11D (ISA-L's and jerasure's field)
and Cauchy parity rows P[i][j] = 1 / ((k+i) xor j), the construction
`garage_tpu/ops/gf256.py` documents.  The field arithmetic here is
written from the definition (shift-and-reduce), not from log tables,
so a slip in either shows as a difference.
"""

import hashlib

import numpy as np

POLY = 0x11D


def block_id(data: bytes) -> str:
    return hashlib.blake2s(data, digest_size=32).hexdigest()


def gf_mul(a: int, b: int, poly: int = POLY) -> int:
    """a·b in GF(2^8): carry-less multiply, reduced by `poly`."""
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= poly
        b >>= 1
    return out


def mul_table(poly: int = POLY) -> np.ndarray:
    """(256, 256) uint8: table[c][x] = c·x."""
    t = np.zeros((256, 256), dtype=np.uint8)
    for c in range(256):
        for x in range(256):
            t[c, x] = gf_mul(c, x, poly)
    return t


_MUL = {}


def _mul(poly: int = POLY) -> np.ndarray:
    if poly not in _MUL:
        _MUL[poly] = mul_table(poly)
    return _MUL[poly]


def gf_inv(a: int, poly: int = POLY) -> int:
    if a == 0:
        raise ZeroDivisionError("no inverse of 0 in GF(2^8)")
    return next(b for b in range(1, 256) if gf_mul(a, b, poly) == 1)


def cauchy_parity_matrix(k: int, m: int, poly: int = POLY) -> np.ndarray:
    return np.array([[gf_inv((k + i) ^ j, poly) for j in range(k)]
                     for i in range(m)], dtype=np.uint8)


def rs_parity(shards: np.ndarray, m: int, poly: int = POLY) -> np.ndarray:
    """(k, S) uint8 data shards → (m, S) parity."""
    k, width = shards.shape
    mat = cauchy_parity_matrix(k, m, poly)
    table = _mul(poly)
    out = np.zeros((m, width), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            out[i] ^= table[mat[i, j]][shards[j]]
    return out


def rs_parity_xor_only(shards: np.ndarray, m: int) -> np.ndarray:
    """The control's parity: every coefficient taken as 1, the field
    cut down to GF(2).  One XOR per byte where the code needs a
    multiply; any single lost shard still decodes, two do not."""
    row = np.bitwise_xor.reduce(shards, axis=0)
    return np.broadcast_to(row, (m, shards.shape[1])).copy()


def codeword_parity(members, maxlen: int, k: int, m: int, parity_fn=rs_parity):
    """Parity of one stored codeword: `members` are the j ≤ k member
    blocks' bytes in order, zero-padded to `maxlen`; absent members of a
    partial codeword are zero shards."""
    shards = np.zeros((k, maxlen), dtype=np.uint8)
    for j, raw in enumerate(members):
        shards[j, :len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    return parity_fn(shards, m)

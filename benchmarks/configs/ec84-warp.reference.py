"""The plain reference of `ec84-warp`, and its control.

What the comparison holds a run to (configs/ec84-warp.json
`guarantees`) is what `ec84-1m` is held to, over members of unequal
lengths: a codeword's parity is GF(2^8) RS(8,4), polynomial 0x11D,
Cauchy rows, of its member blocks each zero-extended to the longest of
them (`benchmarks/reference.py` `codeword_parity`, from the field's
definition), and a block's id is BLAKE2s-256 of its own bytes, however
few.
"""

import numpy as np

from benchmarks.reference import (block_id, codeword_parity,  # noqa: F401
                                  rs_parity_xor_only)


def control_scrub(blocks, hashes, want_parity, k, m):
    """The control: the reference in the program's place, its field cut
    down from GF(2^8) to GF(2) (every coefficient 1: one XOR a byte and
    no multiply, the nearest cheaper arithmetic).  Blocks are verified
    in full, each over its own length.  → (ok per block, parity (rows,
    m, maxlen)), a row's members zero-extended to the batch's longest."""
    ok = [block_id(b) == bytes(h).hex() for b, h in zip(blocks, hashes)]
    if not want_parity:
        return ok, None
    maxlen = max(len(b) for b in blocks)
    rows = -(-len(blocks) // k)
    parity = np.zeros((rows, m, maxlen), dtype=np.uint8)
    for r in range(rows):
        parity[r] = codeword_parity(blocks[r * k:(r + 1) * k], maxlen, k, m,
                                    parity_fn=rs_parity_xor_only)
    return ok, parity

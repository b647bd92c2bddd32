"""The plain reference of `ec84-ingest`, its control, and a model of
the bucket.

What the comparison holds a run to (configs/ec84-ingest.json
`guarantees`): those of `ec84-1m` (a block's id is BLAKE2s-256 of its
bytes; every stored codeword's parity is GF(2^8) RS(8,4), polynomial
0x11D, Cauchy rows, of its member blocks: `benchmarks/reference.py`,
from the field's definition), and the deployment's own: a block whose
PUT was acknowledged before a pass started is verified by that pass,
and when a pass has ended every block it verified is a member of a
stored codeword whose parity is right, but for fewer than k of them.

Nothing here is the program's, and nothing here says how blocks are
grouped into codewords: `codeword_parity` judges whatever codeword a
sidecar states (any j <= k members in the sidecar's own order, absent
members zero shards, members zero-extended to the sidecar's `maxlen`),
and `Bucket` knows objects, their bytes and their blocks' ids only.
"""

import numpy as np

from benchmarks.reference import (block_id, codeword_parity,  # noqa: F401
                                  rs_parity_xor_only)

# The count of `check` (the cell's kind) that the control has to fail
# by, whatever else it fails: the stored parity is not the reference's.
CONTROL_FAILS_BY = "parity_wrong"


def object_bytes(seed: int, index: int, n: int) -> bytes:
    """Every object's content is a function of (seed, index), set-up's
    objects and the ingested ones alike (upstream's smoke payloads:
    dd if=/dev/urandom)."""
    return np.random.default_rng([seed, index]).bytes(n)


class Bucket:
    """A model of the bucket: from the seed, set-up's plan and the PUTs
    that were acknowledged, which keys it holds, the bytes each reads
    back as and the ids of the blocks the node has to hold.  Nothing is
    ever deleted (the configuration's `assumed`: writes only)."""

    def __init__(self, seed: int, block_size: int, plan=()):
        self.seed, self.block_size = seed, block_size
        self.objects = {}           # key -> (index, bytes), in PUT order
        self._ids = {}              # key -> the ids of its blocks
        for key, index, nbytes in plan:
            self.acknowledged(key, index, nbytes)

    def acknowledged(self, key: str, index: int, nbytes: int) -> None:
        """One more object the endpoint said 200 to."""
        self.objects[key] = (index, nbytes)
        self._ids.pop(key, None)

    def reads_as(self, key: str) -> bytes:
        index, nbytes = self.objects[key]
        return object_bytes(self.seed, index, nbytes)

    def ids_of(self, key: str):
        """The ids of the object's blocks, in order."""
        if key not in self._ids:
            body, size = self.reads_as(key), self.block_size
            self._ids[key] = [block_id(body[o:o + size])
                              for o in range(0, len(body), size)]
        return self._ids[key]

    def blocks(self) -> int:
        """How many blocks the bucket's objects are cut into."""
        return sum(-(-nbytes // self.block_size)
                   for _index, nbytes in self.objects.values())

    def block_ids(self) -> set:
        """The ids of every block a node that holds the bucket holds."""
        return {h for key in self.objects for h in self.ids_of(key)}


def control_scrub(blocks, hashes, want_parity, k, m):
    """The control: the reference in the program's place, its field cut
    down from GF(2^8) to GF(2) (every coefficient 1: one XOR a byte and
    no multiply, the nearest cheaper arithmetic).  Blocks are verified
    in full.  → (ok per block, parity (rows, m, maxlen))."""
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

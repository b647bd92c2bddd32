"""The plain reference of `ec84-zst`, and its control.

What the comparison holds a run to (configs/ec84-zst.json
`guarantees`) is what `ec84-1m` is held to, read on CONTENT: a block's
id is BLAKE2s-256 of its content, a codeword's parity is GF(2^8)
RS(8,4), polynomial 0x11D, Cauchy rows, of its members' content
(`benchmarks/reference.py`, from the field's definition).  And the
deployment's own: a block whose content zstd level 1 shrinks is held as
`<id>.zst`, a zstd frame with checksum and content size; any other as
`<id>`; after a heal as before it.

Nothing here is the program's: the payloads are numpy's and base64's,
and a `.zst` file is read by the `zstandard` wheel called directly.
Where the wheel is missing the import fails; the program's shim
(`garage_tpu/utils/zstd_compat.py`, zlib under zstd's name) is never
what a block is read through.
"""

import base64

import numpy as np

from benchmarks.reference import (block_id, codeword_parity,  # noqa: F401
                                  rs_parity_xor_only)

# The count of `check` (the cell's kind) that the control has to fail
# by, whatever else it fails: the stored parity is not the reference's.
CONTROL_FAILS_BY = "parity_wrong"

# The deployment's level, and the frame `DataBlock.from_buffer` writes
# (ref block.rs:80-91): upstream's default, checksum and content size on.
LEVEL = 1
ZST = ".zst"


def payload(seed: int, index: int, n: int, make: str) -> bytes:
    """An object's bytes, a function of (seed, index): `random` as every
    other configuration's (upstream's `.rnd`: dd if=/dev/urandom);
    `base64` the base64, with no line breaks, of random bytes of (seed,
    index), cut to `n` (upstream's `.b64`: "data of lower entropy, to
    test compression")."""
    rng = np.random.default_rng([seed, index])
    if make == "random":
        return rng.bytes(n)
    if make == "base64":
        return base64.b64encode(rng.bytes(-(-n // 4) * 3))[:n]
    raise ValueError(f"no such make of payload: {make!r}")


def content(file_bytes: bytes, name: str) -> bytes:
    """What a block file holds: the frame's content for a `.zst` name
    (raises `zstandard.ZstdError` where the frame does not decode or its
    checksum does not hold), the bytes themselves for any other."""
    if not name.endswith(ZST):
        return file_bytes
    import zstandard

    return zstandard.ZstdDecompressor().decompress(file_bytes)


def stored_form(data: bytes) -> str:
    """The form the deployment holds a block of this content in: `zst`
    where level 1, with checksum and content size, gives fewer bytes."""
    import zstandard

    frame = zstandard.ZstdCompressor(
        level=LEVEL, write_checksum=True,
        write_content_size=True).compress(data)
    return "zst" if len(frame) < len(data) else "plain"


def control_scrub(blocks, hashes, want_parity, k, m):
    """The control: the reference in the program's place, its field cut
    down from GF(2^8) to GF(2) (every coefficient 1: one XOR a byte and
    no multiply, the nearest cheaper arithmetic).  Blocks, which the
    scrub hands over as content, are verified in full.  → (ok per block,
    parity (rows, m, maxlen))."""
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

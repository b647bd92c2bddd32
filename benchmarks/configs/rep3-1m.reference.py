"""The plain reference of `rep3-1m`, and its control.

What the comparison holds a run to (configs/rep3-1m.json `guarantees`):
block ids are hashlib's BLAKE2s-256 of the bytes; an object read back
through S3 is the seeded bytes.  No parity is stored, so none is
compared.
"""

from benchmarks.reference import block_id, codeword_parity  # noqa: F401


def control_scrub(blocks, hashes, want_parity, k, m):
    """The control: the reference in the program's place with one
    guarantee broken.  A scrub that trusts a block that is there and
    has its length, and hashes nothing: the step that would tempt a PR
    after `scrub_mib_s`.  → (ok per block, no parity)."""
    return [len(b) > 0 for b in blocks], None

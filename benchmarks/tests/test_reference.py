"""The plain references against each other, and the controls against
the references, at a tiny size."""

import hashlib
import itertools

import numpy as np

from benchmarks import harness, reference as ref


def test_table_multiply_is_the_scalar_multiply():
    table = ref.mul_table()
    for a, b in [(0, 7), (1, 255), (2, 128), (87, 131), (255, 255)]:
        assert table[a, b] == ref.gf_mul(a, b)
    assert ref.gf_mul(2, 128) == 0x1D          # x * x^7 = x^8 = poly - x^8
    for a in (1, 2, 87, 255):
        assert ref.gf_mul(a, ref.gf_inv(a)) == 1


def test_parity_by_tables_is_parity_byte_by_byte():
    rng = np.random.default_rng(3)
    shards = rng.integers(0, 256, (8, 64), dtype=np.uint8)
    mat = ref.cauchy_parity_matrix(8, 4)
    slow = np.zeros((4, 64), dtype=np.uint8)
    for i, j, s in itertools.product(range(4), range(8), range(64)):
        slow[i, s] ^= ref.gf_mul(int(mat[i, j]), int(shards[j, s]))
    assert np.array_equal(ref.rs_parity(shards, 4), slow)


def test_any_lost_shard_is_recovered_from_one_parity_row():
    """MDS at its smallest: parity row i and the other k-1 shards give
    the lost one back."""
    rng = np.random.default_rng(4)
    shards = rng.integers(0, 256, (8, 32), dtype=np.uint8)
    parity = ref.rs_parity(shards, 4)
    mat = ref.cauchy_parity_matrix(8, 4)
    table = ref.mul_table()
    for lost, row in [(0, 0), (5, 3)]:
        acc = parity[row].copy()
        for j in range(8):
            if j != lost:
                acc ^= table[mat[row, j]][shards[j]]
        got = table[ref.gf_inv(int(mat[row, lost]))][acc]
        assert np.array_equal(got, shards[lost])


def test_block_id_is_blake2s_256():
    assert ref.block_id(b"abc") == hashlib.blake2s(b"abc").hexdigest()
    assert len(ref.block_id(b"")) == 64


def test_codeword_of_fewer_members_pads_with_zero_shards():
    members = [b"\x01\x02\x03", b"\x04"]
    par = ref.codeword_parity(members, 3, 8, 4)
    full = np.zeros((8, 3), dtype=np.uint8)
    full[0] = [1, 2, 3]
    full[1, 0] = 4
    assert np.array_equal(par, ref.rs_parity(full, 4))


def test_the_controls_differ_from_the_references():
    rng = np.random.default_rng(5)
    blocks = [rng.bytes(256) for _ in range(8)]
    hashes = [bytes.fromhex(ref.block_id(b)) for b in blocks]
    bad = bytearray(blocks[2])
    bad[9] ^= 0x40
    blocks[2] = bytes(bad)
    cell = harness.Cell("ec84-1m.scrub")
    ok, parity = cell.reference.control_scrub(blocks, hashes, True, 8, 4)
    assert ok == [True, True, False] + [True] * 5
    assert not np.array_equal(parity[0],
                              ref.codeword_parity(blocks, 256, 8, 4))
    rep3 = harness.Cell("rep3-1m.scrub")
    ok, parity = rep3.reference.control_scrub(blocks, hashes, False, 8, 4)
    assert all(ok) and parity is None       # it trusts the flipped block

"""The scrub under ingest (ISSUE 43), and a codeword that keeps its
members through it (ISSUE 44).

Upstream starts a full pass every 25 to 35 days, so every pass of a node
in use meets blocks the last one never saw.  Here: the second pass
verifies them; every block it verified is then in a stored codeword
whose parity is the reference's, but for fewer than k; and membership is
what the parity index says it is, one test a rule of `block/parity.py`:
a block written between two passes moves no old codeword; a removed
sidecar comes back under its own path, a stray block earlier in the
listing or its members over two batches; a partial write-time codeword
is folded into a full one and purged later, with no block uncovered in
between; a deleted member dissolves its codeword and the survivors are
regrouped; a healed block keeps its codeword and heals from it again;
an index from before the rule converges without a sidecar written; the
counters tell a sidecar of the scrub from one of the write-time
accumulator and from one after a heal, and say what a pass did with
every codeword it met.

The reference (`benchmarks/reference.py`) imports nothing of the
program: a sidecar is judged as the codeword it states (its members, in
its order, at its `maxlen`).
"""

import asyncio
import errno
import hashlib
import os

import msgpack
import numpy as np
import pytest

from benchmarks import reference
from garage_tpu.block import DataBlock
from garage_tpu.block.parity import ParityStore, WriteParityAccumulator
from garage_tpu.block.repair import ScrubWorker
from garage_tpu.db import open_db
from garage_tpu.utils.data import Hash

OLD, NEW = 44, 4        # blocks before the first pass, written after it
SEED = 43


async def _node(tmp_path, flush_after=None):
    """One node with a parity store (and, with `flush_after`, the
    write-time accumulator), its scrub worker, and how to stop it."""
    from tests.test_block import make_block_cluster, shutdown

    systems, (mgr,) = await make_block_cluster(tmp_path, n=1, mode="1")
    mgr.blocks_reconstructed = 0
    mgr.parity_store = ParityStore(mgr, open_db("memory"), mgr.codec)
    if flush_after is not None:
        mgr.write_parity = WriteParityAccumulator(
            mgr.parity_store, mgr.codec, flush_after=flush_after)
    return mgr, ScrubWorker(mgr), lambda: shutdown(systems)


async def _write(mgr, n: int, rng) -> dict:
    """`n` blocks of 3-5 KiB, of unequal lengths."""
    out = {}
    for _ in range(n):
        data = rng.bytes(int(rng.integers(3000, 5000)))
        h = hashlib.blake2s(data, digest_size=32).digest()
        out[h] = data
        await mgr.write_block(Hash(h), DataBlock.plain(data))
    return out


async def _pass(worker):
    worker.send_command("start")
    while (await worker.work()).name in ("BUSY", "THROTTLED"):
        pass


def _sidecars(mgr) -> dict:
    """{path: manifest} of the sidecars on disk."""
    out = {}
    for root, _dirs, names in os.walk(mgr.parity_store.dir):
        for name in names:
            if name.endswith(".par"):
                path = os.path.join(root, name)
                with open(path, "rb") as f:
                    out[path] = msgpack.unpackb(f.read(), raw=False)
    return out


def _judged(mgr, blocks: dict):
    """→ (sidecars whose parity is not the reference's RS of the members
    they state, the ids some sidecar names)."""
    k, m = mgr.codec.params.rs_data, mgr.codec.params.rs_parity
    wrong, named = 0, set()
    for man in _sidecars(mgr).values():
        members = [bytes(h) for h in man["hashes"]]
        assert 0 < len(members) <= k and all(h in blocks for h in members)
        ref = reference.codeword_parity([blocks[h] for h in members],
                                        man["maxlen"], k, m)
        got = np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]])
        wrong += not np.array_equal(ref, got)
        named.update(members)
    return wrong, named


def _counter(mgr, family: str, **labels) -> float:
    return mgr.system.metrics.counter(family).get(**labels)


def _events(mgr, name: str):
    return [e.get("args", {}) for e in mgr.codec.obs.timeline.snapshot()
            if e["name"] == name]


async def _two_passes(tmp_path, new: int = NEW):
    """A pass over OLD blocks, `new` blocks written, a second pass."""
    mgr, worker, stop = await _node(tmp_path)
    rng = np.random.default_rng(SEED)
    blocks = await _write(mgr, OLD, rng)
    await _pass(worker)
    first = _sidecars(mgr)
    blocks.update(await _write(mgr, new, rng))
    verified = _counter(mgr, "scrub_verified_blocks_total")
    written = _counter(mgr, "parity_codewords_written_total", origin="scrub")
    await _pass(worker)
    return mgr, worker, stop, blocks, first, verified, written


@pytest.mark.parametrize("new", [1, NEW])
async def test_blocks_written_between_two_passes_are_verified_by_the_second(
        tmp_path, new):
    mgr, _w, stop, blocks, _first, verified, _wr = await _two_passes(
        tmp_path, new)
    assert _counter(mgr, "scrub_verified_blocks_total") - verified == OLD + new
    last = _events(mgr, "scrub pass")[-1]
    assert last["blocks"] == OLD + new == len(blocks)
    assert last["corruptions"] == 0
    await stop()


@pytest.mark.parametrize("new", [1, NEW])
async def test_every_verified_block_is_in_a_codeword_of_the_references_parity(
        tmp_path, new):
    """But for fewer than k of them; whatever the codewords are."""
    mgr, _w, stop, blocks, _first, _v, _wr = await _two_passes(tmp_path, new)
    wrong, named = _judged(mgr, blocks)
    assert wrong == 0
    assert len(set(blocks) - named) < mgr.codec.params.rs_data
    await stop()


def _members(sidecars: dict) -> dict:
    return {path: [bytes(h) for h in man["hashes"]]
            for path, man in sidecars.items()}


def _named_by(sidecars: dict) -> dict:
    """{block id: the paths of the sidecars that name it}."""
    out = {}
    for path, members in _members(sidecars).items():
        for h in members:
            out.setdefault(h, set()).add(path)
    return out


def _last_pass(mgr) -> dict:
    return _events(mgr, "scrub pass")[-1]


def _states(args: dict) -> dict:
    return {s: args[s] for s in ("settled", "rewritten", "formed",
                                 "dissolved")}


async def _next_pass(worker):
    await asyncio.sleep(0.05)   # mtimes against the next pass's start
    await _pass(worker)


@pytest.mark.parametrize("new", [1, NEW])
async def test_a_block_written_between_two_passes_moves_no_old_codeword(
        tmp_path, new):
    """The second pass writes at most ⌈new / k⌉ sidecars, asks the
    device for those rows' parity and no other, and says so."""
    mgr, _w, stop, _b, first, _v, written = await _two_passes(tmp_path, new)
    k, m = mgr.codec.params.rs_data, mgr.codec.params.rs_parity
    second = _sidecars(mgr)
    assert len(first) == OLD // k
    assert _members(first).items() <= _members(second).items()
    fresh = set(second) - set(first)
    assert len(fresh) == (OLD % k + new) // k <= -(-new // k)
    assert all(len(paths) == 1 for paths in _named_by(second).values())
    last = _last_pass(mgr)
    assert _states(last) == {"settled": len(first), "rewritten": 0,
                             "formed": len(fresh), "dissolved": 0}
    assert last["rows_lacking"] == len(fresh)
    assert last["rows"] == len(second)
    assert (_counter(mgr, "parity_codewords_written_total", origin="scrub")
            - written) == len(fresh)
    assert _counter(mgr, "parity_sidecar_written_bytes_total",
                    origin="scrub") == sum(
        m * man["maxlen"] for man in second.values())
    await stop()


async def test_a_store_that_stood_still_finds_every_sidecar_and_writes_none(
        tmp_path):
    mgr, worker, stop, _b, _first, _v, _wr = await _two_passes(tmp_path)
    written = _counter(mgr, "parity_codewords_written_total", origin="scrub")
    await _pass(worker)
    last = _last_pass(mgr)
    assert last["rows_lacking"] == 0 < last["rows"] == last["settled"]
    assert _counter(mgr, "parity_codewords_written_total",
                    origin="scrub") == written
    await stop()


async def test_a_partial_write_time_codeword_is_folded_and_purged_later(
        tmp_path):
    """Blocks whose only cover is a write-time codeword of fewer than k
    are free: the scrub keeps that sidecar fresh while they wait for a
    full row, folds them into a codeword of its own, and the purge then
    takes the old files, its grace over: no block is without a sidecar
    that names it at any pass's end.  The counter, the `purge stale`
    event and the `scrub pass` event all say how many went."""
    # no timer: a short one fires between two writes on a loaded host
    mgr, worker, stop = await _node(tmp_path, flush_after=60.0)
    k = mgr.codec.params.rs_data
    rng = np.random.default_rng(SEED)
    blocks = await _write(mgr, 3, rng)
    await mgr.write_parity.drain()          # a partial flush: 3 of k
    partial = set(_sidecars(mgr))
    assert len(partial) == 1

    def covered():
        return all(mgr.parity_store.coverage(Hash(h)) for h in blocks)

    await _next_pass(worker)                # nothing to form: 3 wait
    assert _states(_last_pass(mgr)) == dict.fromkeys(
        ("settled", "rewritten", "formed", "dissolved"), 0)
    assert set(_sidecars(mgr)) == partial and covered()
    blocks.update(await _write(mgr, k - 3, rng))
    await mgr.write_parity.drain()
    partial = set(_sidecars(mgr))
    assert len(partial) == 2
    await _next_pass(worker)                # k free blocks: one codeword
    assert _last_pass(mgr)["formed"] == 1
    full = set(_sidecars(mgr)) - partial
    assert len(full) == 1 and covered()
    (members,) = _members({p: _sidecars(mgr)[p] for p in full}).values()
    assert members == sorted(blocks)
    for expect in (partial | full, full):   # the grace, then the purge
        await _next_pass(worker)
        assert set(_sidecars(mgr)) == expect and covered()
        assert _last_pass(mgr)["settled"] == 1
    assert _counter(mgr, "parity_purged_sidecars_total") == 2
    assert _events(mgr, "purge stale")[-1]["removed"] == 2
    assert _last_pass(mgr)["purged"] == 2
    assert sum(e["removed"] for e in _events(mgr, "purge stale")) == 2
    assert _judged(mgr, blocks) == (0, set(blocks))
    await stop()


async def test_a_healed_block_keeps_its_codeword_and_heals_from_it_again(
        tmp_path):
    """A heal writes the block back through the write-time accumulator,
    whose codeword names it too; the index keeps naming the scrub's (a
    full codeword is not robbed), so no codeword is dissolved and the
    next flip of the same block heals from the same sidecar."""
    mgr, worker, stop = await _node(tmp_path, flush_after=60.0)
    k = mgr.codec.params.rs_data
    store = mgr.parity_store
    blocks = await _write(mgr, 2 * k, np.random.default_rng(SEED))
    await mgr.write_parity.settled()        # two write-time codewords
    await _pass(worker)
    assert _last_pass(mgr)["formed"] == 2
    victim = sorted(blocks)[3]
    place = store.index.get(victim)
    assert store._scrub_gid(place) is not None
    scrubs = {p for p, ms in _members(_sidecars(mgr)).items()
              if ms == sorted(ms)}
    assert len(scrubs) == 2
    for heals in (1, 2):
        path, _ = mgr.find_block(Hash(victim))
        bad = bytearray(blocks[victim])
        bad[len(bad) // 2] ^= 0x40
        with open(path, "wb") as f:
            f.write(bytes(bad))
        await _next_pass(worker)
        # one node, no replica: what came back came from a sidecar
        assert mgr.blocks_reconstructed == heals
        assert _counter(mgr, "block_heal_total",
                        source="local_sidecar") == heals
        # the heal's own codeword, of this one member: written once,
        # found the second time
        await mgr.write_parity.drain()
        assert _counter(mgr, "parity_codewords_written_total",
                        origin="heal") == 1
        assert store.index.get(victim) == place
        assert _states(_last_pass(mgr)) == {
            "settled": 2, "rewritten": 0, "formed": 0, "dissolved": 0}
        with open(mgr.find_block(Hash(victim))[0], "rb") as f:
            assert f.read() == blocks[victim]
    assert scrubs <= set(_sidecars(mgr))
    await stop()


async def _three_codewords(tmp_path, batch_blocks=None):
    """A pass over 3 k blocks: three codewords of the scrub's."""
    mgr, worker, stop = await _node(tmp_path)
    if batch_blocks is not None:
        mgr.codec.params.batch_blocks = batch_blocks
    k = mgr.codec.params.rs_data
    rng = np.random.default_rng(SEED)
    blocks = await _write(mgr, 3 * k, rng)
    await _pass(worker)
    first = _sidecars(mgr)
    assert len(first) == 3
    return mgr, worker, stop, blocks, first, rng


async def test_a_removed_sidecar_comes_back_under_its_own_path(tmp_path):
    """With a stray free block earlier in the listing: the codeword's k
    members are encoded again, not k blocks counted off from the stray
    one on."""
    mgr, worker, stop, blocks, first, rng = await _three_codewords(tmp_path)
    last = sorted(blocks)[-1]
    (lost,) = [p for p, ms in _members(first).items() if last in ms]
    stray = {}
    while not stray or min(stray) > sorted(blocks)[0]:
        stray = await _write(mgr, 1, rng)
        blocks.update(stray)
    os.remove(lost)
    written = _counter(mgr, "parity_codewords_written_total", origin="scrub")
    await _next_pass(worker)
    assert _members(_sidecars(mgr)) == _members(first)
    assert _states(_last_pass(mgr)) == {
        "settled": 2, "rewritten": 1, "formed": 0, "dissolved": 0}
    assert _last_pass(mgr)["rows_lacking"] == 1
    assert _last_pass(mgr)["rows_host"] == 0
    assert (_counter(mgr, "parity_codewords_written_total", origin="scrub")
            - written) == 1
    wrong, named = _judged(mgr, blocks)
    assert wrong == 0 and set(blocks) - named == set(blocks) - set(
        h for ms in _members(first).values() for h in ms)
    await stop()


async def test_a_sidecar_lost_while_the_purge_runs_keeps_its_members(
        tmp_path):
    """The purge prunes index entries whose sidecar is gone; a scrub
    codeword's survive it while their blocks are in the store (whoever
    polls the worker's state sees a pass ended before its purge has
    run, and may remove a sidecar under it), and go with their block."""
    mgr, worker, stop, blocks, first, _rng = await _three_codewords(tmp_path)
    store = mgr.parity_store
    lost = sorted(first)[1]
    members = _members(first)[lost]
    os.remove(lost)
    os.remove(mgr.find_block(Hash(members[0]))[0])
    store.purge_stale(0.0)
    assert store.last_purge == {"removed": 0, "dead": 1}
    assert store.index.get(members[0]) is None
    assert all(store._scrub_gid(store.index.get(h)) for h in members[1:])
    await mgr.write_block(Hash(members[0]),
                          DataBlock.plain(blocks[members[0]]))
    os.remove(sorted(first)[2])
    store.purge_stale(0.0)
    assert store.last_purge == {"removed": 0, "dead": 0}
    await _next_pass(worker)
    # the one whose members are all indexed is back under its name; the
    # other has a member the index lost: dissolved, regrouped next pass
    assert _states(_last_pass(mgr)) == {
        "settled": 1, "rewritten": 1, "formed": 0, "dissolved": 1}
    assert set(_sidecars(mgr)) == set(first) - {lost}
    await _next_pass(worker)
    assert _last_pass(mgr)["formed"] == 1
    assert _members(_sidecars(mgr)) == _members(first)
    assert _judged(mgr, blocks) == (0, set(blocks))
    await stop()


async def test_a_codeword_over_two_batches_is_rewritten_whole(tmp_path):
    """Every sidecar removed, batches shorter than a codeword: the
    members read first are kept until the last is, and each file comes
    back under its name with the reference's parity."""
    mgr, worker, stop, blocks, first, _rng = await _three_codewords(
        tmp_path, batch_blocks=5)
    for path in first:
        os.remove(path)
    await _next_pass(worker)
    last = _last_pass(mgr)
    assert last["batches"] >= 4
    assert _states(last) == {"settled": 0, "rewritten": 3, "formed": 0,
                             "dissolved": 0}
    # members of two batches: none of the three was the device's to encode
    assert last["rows_host"] == last["rows_lacking"] == 3
    assert sum(e["host"] for e in _events(mgr, "parity write")[-3:]) == 3
    assert _members(_sidecars(mgr)) == _members(first)
    assert _judged(mgr, blocks) == (0, set(blocks))
    await stop()


async def test_a_deleted_member_dissolves_its_codeword_at_the_passs_end(
        tmp_path):
    """The survivors keep the old sidecar as their cover, are regrouped
    by the next pass that has k free blocks, and the old file goes when
    the purge's grace is over."""
    mgr, worker, stop, blocks, first, rng = await _three_codewords(tmp_path)
    store = mgr.parity_store
    gone = sorted(blocks)[10]
    (old,) = [p for p, ms in _members(first).items() if gone in ms]
    os.remove(mgr.find_block(Hash(gone))[0])
    del blocks[gone]
    await _next_pass(worker)
    assert _states(_last_pass(mgr)) == {
        "settled": 2, "rewritten": 0, "formed": 0, "dissolved": 1}
    assert set(_sidecars(mgr)) == set(first)
    survivors = [h for h in _members(first)[old] if h != gone]
    assert all(store.coverage(Hash(h)) for h in blocks)
    assert all(store._scrub_gid(store.index.get(h)) is None
               for h in survivors)
    blocks.update(await _write(mgr, 1, rng))    # k free blocks now
    await _next_pass(worker)
    assert _states(_last_pass(mgr)) == {
        "settled": 2, "rewritten": 0, "formed": 1, "dissolved": 0}
    (new,) = set(_sidecars(mgr)) - set(first)
    assert set(_members(_sidecars(mgr))[new]) == set(survivors) | (
        set(blocks) - set(h for ms in _members(first).values() for h in ms))
    assert old in _sidecars(mgr)
    for _ in range(2):
        await _next_pass(worker)
        assert _last_pass(mgr)["settled"] == 3
    assert old not in _sidecars(mgr)
    assert _counter(mgr, "parity_purged_sidecars_total") == 1
    assert _judged(mgr, blocks) == (0, set(blocks))
    await stop()


@pytest.mark.parametrize("eno, batch_blocks, settled", [
    (errno.EMFILE, None, 2),    # a transient error: the read gives None
    (errno.EIO, 1, 3),          # a media error, in a batch of its own
])
async def test_a_member_not_read_this_once_dissolves_nothing(
        tmp_path, eno, batch_blocks, settled):
    """Not read is not gone: a codeword a whole pass read fewer than k
    members of keeps them while the store still has the one it missed,
    and a block the disk could not read (healed from the sidecar) is a
    member read, in a batch that holds nothing else too."""
    from garage_tpu.testing.faults import FaultyDisk

    mgr, worker, stop, blocks, first, _rng = await _three_codewords(
        tmp_path, batch_blocks=batch_blocks)
    store = mgr.parity_store
    index = dict(store.index.items(None, None))
    missed = sorted(blocks)[10]
    mgr.disk = FaultyDisk(mgr.disk,
                          path_prefix=mgr.find_block(Hash(missed))[0])
    mgr.disk.read_errno = eno
    written = _counter(mgr, "parity_codewords_written_total", origin="scrub")
    await _next_pass(worker)
    assert mgr.disk.injected["read"] == 1
    assert _states(_last_pass(mgr)) == {
        "settled": settled, "rewritten": 0, "formed": 0, "dissolved": 0}
    assert dict(store.index.items(None, None)) == index
    mgr.disk.clear()
    await _next_pass(worker)
    assert _states(_last_pass(mgr)) == {
        "settled": 3, "rewritten": 0, "formed": 0, "dissolved": 0}
    assert _counter(mgr, "parity_codewords_written_total",
                    origin="scrub") == written
    assert _members(_sidecars(mgr)) == _members(first)
    assert _judged(mgr, blocks) == (0, set(blocks))
    await stop()


async def test_an_index_from_before_the_rule_converges_in_one_pass(tmp_path):
    """Entries of the bare 32 bytes, as the code before wrote them, and a
    block written since: the pass writes no sidecar, finds every
    codeword settled and gives its members' entries the suffix."""
    mgr, worker, stop, blocks, first, rng = await _three_codewords(tmp_path)
    store = mgr.parity_store
    for key, entry in list(store.index.items(None, None)):
        assert len(entry) == 34
        store.index.insert(key, entry[:32])
    blocks.update(await _write(mgr, 1, rng))
    written = _counter(mgr, "parity_codewords_written_total", origin="scrub")
    await _next_pass(worker)
    assert _counter(mgr, "parity_codewords_written_total",
                    origin="scrub") == written
    assert _states(_last_pass(mgr)) == {
        "settled": 3, "rewritten": 0, "formed": 0, "dissolved": 0}
    assert _last_pass(mgr)["rows_lacking"] == 0
    assert _members(_sidecars(mgr)) == _members(first)
    assert sorted(len(e) for _k, e in store.index.items(None, None)) == [
        34] * (3 * mgr.codec.params.rs_data)
    await stop()


async def test_scrub_codewords_total_sums_to_the_passs_rows(tmp_path):
    """Every series from 0; after each pass the four states' growth is
    the `scrub pass` event's four counts, and their sum its `rows`."""
    mgr, worker, stop = await _node(tmp_path)
    states = ("settled", "rewritten", "formed", "dissolved")

    def counted():
        return {s: _counter(mgr, "scrub_codewords_total", state=s)
                for s in states}

    assert counted() == dict.fromkeys(states, 0)
    rng = np.random.default_rng(SEED)
    blocks = await _write(mgr, OLD, rng)
    seen = []
    for step in range(4):
        before = counted()
        await _next_pass(worker)
        last = _last_pass(mgr)
        grown = {s: n - before[s] for s, n in counted().items()}
        assert grown == _states(last)
        assert sum(grown.values()) == last["rows"] > 0
        seen.append(grown)
        if step == 0:       # a sidecar lost, blocks written
            os.remove(sorted(_sidecars(mgr))[0])
            blocks.update(await _write(mgr, NEW, rng))
        elif step == 1:     # a member deleted for good
            os.remove(mgr.find_block(Hash(sorted(blocks)[0]))[0])
    assert [g["formed"] for g in seen] == [OLD // 8, 1, 0, 0]
    assert [g["rewritten"] for g in seen] == [0, 1, 0, 0]
    assert [g["dissolved"] for g in seen] == [0, 0, 1, 0]
    assert [g["settled"] for g in seen] == [0, OLD // 8 - 1, OLD // 8,
                                            OLD // 8]
    await stop()


async def test_the_written_counters_tell_scrub_write_time_and_heal_apart(
        tmp_path):
    mgr, worker, stop = await _node(tmp_path, flush_after=60.0)
    k, m = mgr.codec.params.rs_data, mgr.codec.params.rs_parity
    rng = np.random.default_rng(SEED)

    def written():
        return {o: (_counter(mgr, "parity_codewords_written_total", origin=o),
                    _counter(mgr, "parity_sidecar_written_bytes_total",
                             origin=o))
                for o in ("scrub", "write", "heal")}

    blocks = await _write(mgr, k, rng)          # the k-th flushes: `full`
    await mgr.write_parity.settled()
    longest = max(map(len, blocks.values()))
    assert written() == {"scrub": (0, 0), "write": (1, m * longest),
                         "heal": (0, 0)}
    blocks.update(await _write(mgr, 2 * k, rng))
    await mgr.write_parity.settled()
    assert written()["write"][0] == 3
    await _pass(worker)                         # the scrub's own codewords
    scrub = written()["scrub"]
    assert scrub[0] >= 1 and written()["heal"] == (0, 0)
    # a flip, a pass: the heal writes the block back through the
    # accumulator, whose codeword of one healed member is a heal's
    victim = sorted(blocks)[1]
    path, _ = mgr.find_block(Hash(victim))
    with open(path, "r+b") as f:
        f.write(b"\xff" if blocks[victim][0] != 0xFF else b"\x00")
    await _pass(worker)
    assert mgr.blocks_reconstructed == 1
    await mgr.write_parity.drain()
    now = written()
    assert now["heal"] == (1, m * len(blocks[victim]))
    assert now["write"][0] == 3
    flush = _events(mgr, "write parity flush")[-1]
    assert flush == {"members": 1, "partial": True, "cause": "drain",
                     "healed": 1}
    wrong, _named = _judged(mgr, blocks)
    assert wrong == 0
    await stop()


@pytest.mark.parametrize("cause, members", [("full", 8), ("timeout", 3),
                                            ("drain", 2)])
async def test_the_accumulators_flush_event_carries_its_cause(
        tmp_path, cause, members):
    mgr, _worker, stop = await _node(
        tmp_path, flush_after=0.5 if cause == "timeout" else 60.0)
    k = mgr.codec.params.rs_data
    assert k == 8
    blocks = await _write(mgr, members, np.random.default_rng(SEED))
    if cause == "drain":
        await mgr.write_parity.drain()
    else:
        await mgr.write_parity.settled()
    assert _events(mgr, "write parity flush") == [
        {"members": members, "partial": members < k, "cause": cause,
         "healed": 0}]
    assert _counter(mgr, "write_parity_flushes_total", cause=cause) == 1
    assert all(mgr.parity_store.coverage(Hash(h)) for h in blocks)
    await stop()

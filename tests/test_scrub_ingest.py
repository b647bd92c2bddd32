"""The scrub under ingest (ISSUE 43): blocks written between two passes.

Upstream starts a full pass every 25 to 35 days, so every pass of a node
in use meets blocks the last one never saw.  Here: the second pass
verifies them; every block it verified is then in a stored codeword
whose parity is the reference's, but for fewer than k; the sidecars of
codewords that are no more are purged and counted; a flipped old block
heals from a sidecar after its codeword moved; the counters tell a
sidecar of the scrub from one of the write-time accumulator and from one
after a heal; the accumulator's flush says what caused it.

The reference (`benchmarks/reference.py`) imports nothing of the
program, and nothing here says how the program groups blocks into
codewords: a sidecar is judged as the codeword it states (its members,
in its order, at its `maxlen`), and "moved" is read off the disk (a
block that two sidecars name).
"""

import asyncio
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


async def test_the_second_pass_writes_what_lacked_a_sidecar_and_says_so(
        tmp_path):
    mgr, _w, stop, _b, first, _v, written = await _two_passes(tmp_path)
    second = _sidecars(mgr)
    fresh = set(second) - set(first)
    assert fresh, "no block written between the passes changed a codeword"
    last = _events(mgr, "scrub pass")[-1]
    assert last["rows_lacking"] == len(fresh) <= last["rows"]
    assert (_counter(mgr, "parity_codewords_written_total", origin="scrub")
            - written) == len(fresh)
    k, m = mgr.codec.params.rs_data, mgr.codec.params.rs_parity
    assert _counter(mgr, "parity_sidecar_written_bytes_total",
                    origin="scrub") == sum(
        m * man["maxlen"] for man in second.values())
    assert k * len(second) >= OLD  # and the first pass's are still there
    await stop()


async def test_a_store_that_stood_still_finds_every_sidecar_and_writes_none(
        tmp_path):
    mgr, worker, stop, _b, _first, _v, _wr = await _two_passes(tmp_path)
    written = _counter(mgr, "parity_codewords_written_total", origin="scrub")
    await _pass(worker)
    last = _events(mgr, "scrub pass")[-1]
    assert last["rows_lacking"] == 0 < last["rows"]
    assert _counter(mgr, "parity_codewords_written_total",
                    origin="scrub") == written
    await stop()


async def test_what_moved_is_purged_a_pass_later_and_counted(tmp_path):
    """The purge's grace is one pass: the sidecars the second pass did
    not refresh go at the end of the third, and the counter, the `purge
    stale` event and the `scrub pass` event all say how many."""
    mgr, worker, stop, blocks, first, _v, _wr = await _two_passes(tmp_path)
    second = _sidecars(mgr)
    assert _counter(mgr, "parity_purged_sidecars_total") == 0
    assert set(first) <= set(second)
    await asyncio.sleep(0.05)       # mtimes against the third pass's start
    await _pass(worker)
    third = _sidecars(mgr)
    gone = set(second) - set(third)
    assert gone and gone <= set(first)
    assert _counter(mgr, "parity_purged_sidecars_total") == len(gone)
    assert _events(mgr, "purge stale")[-1]["removed"] == len(gone)
    assert _events(mgr, "scrub pass")[-1]["purged"] == len(gone)
    assert sum(e["removed"] for e in _events(mgr, "purge stale")) == len(gone)
    wrong, named = _judged(mgr, blocks)
    assert wrong == 0
    assert len(set(blocks) - named) < mgr.codec.params.rs_data
    await stop()


async def test_a_flipped_old_block_heals_from_a_sidecar_after_its_codeword_moved(
        tmp_path):
    mgr, worker, stop, blocks, first, _v, _wr = await _two_passes(tmp_path)
    names = {}
    for path, man in _sidecars(mgr).items():
        for h in man["hashes"]:
            names.setdefault(bytes(h), set()).add(path)
    # moved, as the disk shows it: a block of the first pass that a
    # second sidecar names now
    moved = sorted(h for h, paths in names.items()
                   if len(paths) > 1 and paths & set(first))
    assert moved, "no codeword moved"
    victim = moved[len(moved) // 2]
    path, _ = mgr.find_block(Hash(victim))
    bad = bytearray(blocks[victim])
    bad[len(bad) // 2] ^= 0x40
    with open(path, "wb") as f:
        f.write(bytes(bad))
    await _pass(worker)
    assert worker.state.corruptions == 1
    # one node, no replica: what came back came from a sidecar
    assert mgr.blocks_reconstructed == 1
    assert _counter(mgr, "block_heal_total", source="local_sidecar") == 1
    with open(mgr.find_block(Hash(victim))[0], "rb") as f:
        assert f.read() == blocks[victim]
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
        tmp_path, flush_after=0.05 if cause == "timeout" else 60.0)
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

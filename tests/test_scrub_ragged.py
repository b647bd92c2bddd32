"""A scrub pass over blocks as clients leave them (ISSUE 29): short last
blocks, so ragged lanes in every batch and codewords whose members
differ in length, through ScrubWorker → feeder → transport → pool →
the device codec (on the CPU's devices here).  Held to hashlib and to
RS(8,4) written from the field's definition (`benchmarks/reference.py`,
which imports nothing of the program), and to the counters' own sums.

An object under INLINE_THRESHOLD has no block and so nothing to scrub:
the tiny `ec84-warp` cell (`benchmarks/tests/test_cells_sized.py`)
writes and reads those back through S3.
"""

import asyncio
import hashlib
import os
import time

import msgpack
import numpy as np
import pytest

from benchmarks import reference
from garage_tpu.block import BlockManager, DataBlock
from garage_tpu.block.parity import ParityStore
from garage_tpu.block.repair import ScrubWorker
from garage_tpu.db import open_db
from garage_tpu.ops import make_codec
from garage_tpu.rpc.replication_mode import parse_replication_mode
from garage_tpu.table import TableShardedReplication
from garage_tpu.utils.data import Hash

K, M = 8, 4
BLOCK = 64 << 10
# 26 objects of 4-256 KiB, log2-uniform: 41 blocks, 15 whole and 26 short,
# 1.54 MiB against a pool of 1 MiB, so that a pass reads the disk (a block
# the pool still holds is verified there: ROADMAP queue 1 item 9)
OBJECTS = 26
BLOCKS = 41
SEED = 29


def object_sizes():
    lo, hi = 12.0, 18.0
    return [int(2.0 ** (lo + (i + 0.5) / OBJECTS * (hi - lo)))
            for i in range(OBJECTS)]


def cut_into_blocks():
    rng = np.random.default_rng(SEED)
    out = []
    for n in object_sizes():
        body = rng.bytes(n)
        out += [body[o:o + BLOCK] for o in range(0, n, BLOCK)]
    return out


async def _pass(worker):
    worker.send_command("start")
    while (await worker.work()).name in ("BUSY", "THROTTLED"):
        pass


def _sidecars(data_dir):
    out = []
    for root, _dirs, names in os.walk(os.path.join(data_dir, "parity")):
        for name in sorted(names):
            if name.endswith(".par"):
                with open(os.path.join(root, name), "rb") as f:
                    out.append(msgpack.unpackb(f.read(), raw=False))
    return out


async def _run(tmp_path) -> dict:
    from tests.test_table import make_cluster, shutdown

    (system,) = await make_cluster(tmp_path, n=1, mode="1")
    codec = make_codec(
        "hybrid", metrics=system.metrics, tracer=system.tracer,
        block_size=BLOCK, rs_data=K, rs_parity=M, batch_blocks=16,
        pool_mib=1, pool_page_kib=16,
        # the gate's verdict on the CPU's "device" is a wall-clock rate:
        # where the checkout's compile cache is warm the first probe can
        # read `hold` and the pass's first batch goes to the CPU side
        # (3 rows fetched for 5: one run in four to eight); this test is
        # about the device road, so the gate opens at any rate
        hybrid_min_link_gibs=0.0)
    deadline = time.monotonic() + 120
    while not (codec.info().get("device_attached")
               and codec.info().get("transport")):
        assert time.monotonic() < deadline, "device codec did not attach"
        await asyncio.sleep(0.05)
    mode = parse_replication_mode("1")
    system.config.data_dir = [{"path": str(tmp_path / "n0" / "data")}]
    mgr = BlockManager(
        system.config, open_db("memory"), system,
        TableShardedReplication(system, mode.replication_factor, 1,
                                mode.write_quorum), codec=codec)
    mgr.blocks_reconstructed = 0
    mgr.parity_store = ParityStore(mgr, open_db("memory"), codec)
    blocks = {}
    for b in cut_into_blocks():
        h = hashlib.blake2s(b, digest_size=32).digest()
        blocks[h] = b
        await mgr.write_block(Hash(h), DataBlock.plain(b))
    got = {"blocks": blocks, "lengths": sorted(map(len, blocks.values()))}
    reg, tl = system.metrics, codec.obs.timeline

    def lane_bytes(part):
        return reg.counter("transport_lane_bytes_total").get(
            kind="scrub", part=part)

    def sidecar_bytes(part):
        return reg.counter("parity_sidecar_bytes_total").get(part=part)

    worker = ScrubWorker(mgr)
    try:
        await _pass(worker)
        got["first_corruptions"] = worker.state.corruptions
        got["sidecars"] = _sidecars(str(tmp_path / "n0" / "data"))
        got["submits"] = [e["args"] for e in tl.snapshot()
                          if e["name"] == "submit scrub"]
        got["lane_bytes"] = {p: lane_bytes(p) for p in ("payload", "pad")}
        got["sidecar_bytes"] = {p: sidecar_bytes(p)
                                for p in ("parity", "covered")}
        got["verified"] = (worker.m_bytes.get(), worker.m_blocks.get())
        got["tpu_bytes"] = codec.info()["bytes"]["tpu"]

        # a short member of a codeword whose longest member is longer:
        # of the first such row in id order, which the pool has let go
        man = min((m for m in got["sidecars"]
                   if min(m["lengths"]) < m["maxlen"]),
                  key=lambda m: bytes(m["hashes"][0]))
        i = man["lengths"].index(min(man["lengths"]))
        victim = bytes(man["hashes"][i])
        assert codec.pool.lookup(victim, man["lengths"][i]) is None
        path, _ = mgr.find_block(Hash(victim))
        bad = bytearray(blocks[victim])
        bad[len(bad) // 2] ^= 0x40
        with open(path, "wb") as f:
            f.write(bytes(bad))
        q0 = reg.counter("block_quarantine_total").get()
        await _pass(worker)
        got["victim"] = (victim, man["lengths"][i], man["maxlen"])
        got["second_corruptions"] = worker.state.corruptions
        got["quarantined"] = reg.counter("block_quarantine_total").get() - q0
        got["reconstructed"] = mgr.blocks_reconstructed
        got["heals"] = [e["args"] for e in tl.snapshot()
                        if e["name"] == "quarantine+heal"]
        with open(mgr.find_block(Hash(victim))[0], "rb") as f:
            got["healed"] = f.read()

        # a third pass, two sidecars removed before it (ISSUE 33): their
        # rows' parity is all that leaves the device
        def counted():
            return {
                "rows": {f: reg.counter("scrub_parity_rows_total").get(
                    fetch=f) for f in ("fetched", "left")},
                "row_programs": reg.counter("pool_programs_total").get(
                    op="parity_row"),
                "sidecar_bytes": {p: sidecar_bytes(p)
                                  for p in ("parity", "covered")}}

        got["after_two"] = counted()
        files = sorted(os.path.join(d, n) for d, _s, ns in os.walk(
            mgr.parity_store.dir) for n in ns)
        for f in files:
            os.utime(f, (1, 1))
        os.remove(files[0])
        os.remove(files[3])
        await _pass(worker)
        got["after_three"] = counted()
        got["third_mtimes"] = [os.stat(f).st_mtime for f in files]
        got["third_sidecars"] = _sidecars(str(tmp_path / "n0" / "data"))
        got["third_corruptions"] = worker.state.corruptions
    finally:
        if mgr.feeder is not None:
            mgr.feeder.shutdown()
        codec.close()
        await shutdown([system])
    return got


@pytest.fixture(scope="module")
def passes(tmp_path_factory):
    return asyncio.run(_run(tmp_path_factory.mktemp("ragged")))


def check_digests(got):
    """Every block is stored under hashlib's BLAKE2s-256 of its bytes,
    and the pass, on the device, found none that differs."""
    assert len(got["blocks"]) == BLOCKS
    assert sum(n == BLOCK for n in got["lengths"]) == 15
    assert got["lengths"][0] < 8192 and got["first_corruptions"] == 0
    assert got["tpu_bytes"] >= sum(got["lengths"])
    assert got["submits"] and all(s["lanes"] >= 8 for s in got["submits"])


def check_sidecars(got):
    """Every codeword of k members in id order, each parity row as long
    as the row's longest member and equal to the reference's."""
    sidecars = got["sidecars"]
    assert len(sidecars) == BLOCKS // K
    ids = sorted(got["blocks"])
    unequal = 0
    for man in sidecars:
        members = [bytes(h) for h in man["hashes"]]
        raws = [got["blocks"][h] for h in members]
        assert members == ids[ids.index(members[0]):][:K]
        assert man["lengths"] == [len(r) for r in raws]
        assert man["maxlen"] == max(man["lengths"])
        ref = reference.codeword_parity(raws, man["maxlen"], K, M)
        have = np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]])
        assert np.array_equal(ref, have)
        unequal += len(set(man["lengths"])) > 1
    assert unequal >= 3     # members that differ are the rule here


def check_heal(got):
    """A flipped short member is quarantined and rebuilt from a sidecar
    whose other members are longer: decoded at the row's width, cut to
    the member's own length, byte-identical."""
    victim, length, maxlen = got["victim"]
    assert length < maxlen
    assert got["second_corruptions"] == 1 and got["quarantined"] == 1
    assert got["reconstructed"] == 1
    assert got["heals"] == [{"blocks": 1, "how": "local_sidecar",
                             "local_sidecar": 1}]
    assert got["healed"] == got["blocks"][victim]
    assert len(got["healed"]) == length


def check_refetch(got):
    """Two sidecars removed before a pass: 2 of its 5 rows cross the
    link, a dispatch of the geometry's row program each, and 3 stay;
    the pass leaves the first pass's sidecars, every file touched, the
    same bytes counted as filed."""
    before, after = got["after_two"], got["after_three"]
    # the first pass found no sidecar: every row of it was fetched
    assert before["rows"]["fetched"] >= BLOCKS // K
    rows = {f: after["rows"][f] - before["rows"][f] for f in after["rows"]}
    # `left` counts a batch's trailing members too, no codeword yet
    assert rows["fetched"] == 2 and 3 <= rows["left"] <= 3 + len(
        got["submits"])
    # (a dispatch a row; the whole array where a batch wants most of its rows)
    assert after["row_programs"] - before["row_programs"] in (0, 1, 2)
    assert got["third_corruptions"] == 0
    assert got["third_sidecars"] == got["sidecars"]
    assert min(got["third_mtimes"]) > 1
    assert {p: after["sidecar_bytes"][p] - before["sidecar_bytes"][p]
            for p in ("parity", "covered")} == got["sidecar_bytes"]


def check_counters(got):
    """pad + payload is what the slots handed over; covered is the
    members' lengths and parity m rows of each codeword's longest."""
    submits, lanes = got["submits"], got["lane_bytes"]
    assert lanes["payload"] == sum(s["payload_bytes"] for s in submits)
    assert (lanes["payload"] + lanes["pad"]
            == sum(s["staged_bytes"] for s in submits))
    assert lanes["pad"] > 0
    for s in submits:       # a slot is miss rows of the batch's width
        assert s["staged_bytes"] % s["shape"][1] == 0
        assert s["payload_bytes"] <= s["staged_bytes"]
    nbytes, nblocks = got["verified"]
    assert (nbytes, nblocks) == (sum(got["lengths"]), BLOCKS)
    side = got["sidecar_bytes"]
    assert side["covered"] == sum(sum(m["lengths"]) for m in got["sidecars"])
    assert side["parity"] == sum(M * m["maxlen"] for m in got["sidecars"])
    assert side["parity"] * K > side["covered"] * M     # over RS(8,4)'s 50%


@pytest.mark.parametrize("check", [check_digests, check_sidecars, check_heal,
                                   check_refetch, check_counters],
                         ids=lambda f: f.__name__[6:])
def test_ragged_scrub_pass(passes, check):
    check(passes)


@pytest.mark.parametrize("shape", [(1, 4, 1024), (3, 8, 7), (2, 1)])
def test_host_views_are_the_device_views(shape):
    """`host_words` and `host_bytes` (numpy views either side of the
    link): the first against the device-side bitcast it stands in for
    outside a jit, the second as its inverse."""
    import jax.numpy as jnp

    from garage_tpu.ops.tpu_codec import (bytes_view_u32, host_bytes,
                                          host_words)

    rng = np.random.default_rng(len(shape))
    raw = rng.integers(0, 256, shape[:-1] + (4 * shape[-1],), dtype=np.uint8)
    words = host_words(raw)
    assert words.shape == shape and words.dtype == np.uint32
    assert np.array_equal(words, np.asarray(bytes_view_u32(jnp.asarray(raw))))
    assert np.array_equal(host_bytes(jnp.asarray(words)), raw)

"""Block store tests: DataBlock codec, DataLayout, BlockManager RPC
get/put on a real 3-node loopback cluster, refcounting, resync
(missing-fetch and offload), and the batch-first scrub worker."""

import asyncio
import os

import pytest

from garage_tpu.block import (
    BlockManager,
    BlockResyncManager,
    DataBlock,
    DataLayout,
    ScrubWorker,
)
from garage_tpu.block.layout import DRIVE_NPART, drive_partition
from garage_tpu.block.repair import (
    BlockStoreIterator,
    RebalanceWorker,
    ScrubWorkerState,
)
from garage_tpu.block.resync import ResyncPersistedConfig
from garage_tpu.utils.persister import Persister
from garage_tpu.db import open_db
from garage_tpu.rpc.replication_mode import parse_replication_mode
from garage_tpu.table import TableShardedReplication
from garage_tpu.utils.data import Hash, blake2s_sum, gen_uuid
from garage_tpu.utils.error import CorruptData, GarageError

from tests.test_table import make_cluster, shutdown

pytestmark = pytest.mark.asyncio


# --- DataBlock ---


def test_datablock_plain_verify():
    data = os.urandom(4096)
    h = blake2s_sum(data)
    b = DataBlock.plain(data)
    b.verify(h)  # ok
    with pytest.raises(CorruptData):
        DataBlock.plain(data[:-1] + b"\x00").verify(h)


def test_datablock_compression_roundtrip():
    data = b"a" * 100_000  # compressible
    b = DataBlock.from_buffer(data, compression_level=3)
    assert b.compressed and len(b) < len(data)
    assert b.decompressed() == data
    b.verify(blake2s_sum(data))  # zstd checksum path
    # corrupted frame fails
    bad = DataBlock(b.inner[:-2] + b"\x00\x00", compressed=True)
    with pytest.raises(CorruptData):
        bad.verify(blake2s_sum(data))
    # incompressible data stays plain
    rnd = os.urandom(100_000)
    assert not DataBlock.from_buffer(rnd, compression_level=3).compressed
    assert not DataBlock.from_buffer(rnd, compression_level=None).compressed


# --- DataLayout ---


def test_data_layout_assignment(tmp_path):
    d1, d2 = str(tmp_path / "d1"), str(tmp_path / "d2")
    lay = DataLayout.initialize([{"path": d1, "capacity": 100}, {"path": d2, "capacity": 300}])
    counts = [0, 0]
    for p in lay.part_prim:
        counts[p] += 1
    assert sum(counts) == DRIVE_NPART
    assert abs(counts[1] - 3 * counts[0]) <= 4  # ∝ capacity
    # deterministic: same config → same assignment
    lay2 = DataLayout.initialize([{"path": d1, "capacity": 100}, {"path": d2, "capacity": 300}])
    assert lay.part_prim == lay2.part_prim
    # update: moved partitions keep old dir as secondary
    lay3 = lay.update([{"path": d1, "capacity": 300}, {"path": d2, "capacity": 100}])
    moved = [p for p in range(DRIVE_NPART) if lay3.part_prim[p] != lay.part_prim[p]]
    assert moved, "capacity flip must move partitions"
    for p in moved:
        assert lay.part_prim[p] in lay3.part_sec[p]
    # persistence roundtrip
    enc = lay3.encode()
    assert DataLayout.decode(enc).part_prim == lay3.part_prim


# --- cluster harness ---


async def make_block_cluster(tmp_path, n=3, mode="3"):
    systems = await make_cluster(tmp_path, n=n, mode=mode)
    m = parse_replication_mode(mode)
    managers = []
    for i, s in enumerate(systems):
        db = open_db("memory")
        repl = TableShardedReplication(s, m.replication_factor, 1, m.write_quorum)
        s.config.data_dir = [{"path": str(tmp_path / f"n{i}" / "data")}]
        mgr = BlockManager(s.config, db, s, repl)
        mgr.resync = BlockResyncManager(mgr, db)
        managers.append(mgr)
    return systems, managers


async def test_block_put_get_roundtrip(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(200_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.1)  # straggler drain
    stored = sum(1 for m in managers if m.is_block_present(h))
    assert stored == 3
    for m in managers:
        got = await m.rpc_get_block(h)
        assert got == data
    await shutdown(systems)


async def test_block_get_tries_other_nodes(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(50_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.1)
    # delete the local copy on one node; its reads must hit the network
    victim = managers[1]
    found = victim.find_block(h)
    if found:
        os.remove(found[0])
    got = await victim.rpc_get_block(h)
    assert got == data
    await shutdown(systems)


async def test_corrupt_block_detected_and_requeued(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(150_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.1)
    m = next(m for m in managers if m.is_block_present(h))
    path, _ = m.find_block(h)
    with open(path, "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(CorruptData):
        await m.read_block(h)
    assert not m.is_block_present(h)  # moved aside
    assert os.path.exists(path + ".corrupted")
    assert m.resync.queue_len() == 1  # requeued for re-fetch
    await shutdown(systems)


async def test_quarantine_resync_reserve_loop(tmp_path):
    """Corrupt a stored copy on disk (FaultInjector.corrupt_block): the
    client read still returns correct bytes (failover), the bad copy is
    quarantined, and after the queued resync runs a later read serves a
    healed LOCAL copy."""
    from garage_tpu.testing.faults import FaultInjector

    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(120_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.1)
    inj = FaultInjector([], configs=[s.config for s in systems])
    i, m = next((i, m) for i, m in enumerate(managers)
                if m.is_block_present(h))
    path, _ = m.find_block(h)
    assert inj.corrupt_block(i, h)
    # the client gets correct bytes — the corrupt local copy fails
    # verify, is quarantined, and the read fails over to a replica
    assert await m.rpc_get_block(h) == data
    assert os.path.exists(path + ".corrupted")
    assert not m.is_block_present(h)
    assert m.quarantined == 1
    assert m.resync.enqueue_counts.get("corrupt_read") == 1
    # drive the queued refetch; a later read serves the healed copy
    m.db.transaction(lambda tx: m.rc.block_incref(tx, h))
    await m.resync.resync_block(h)
    assert m.is_block_present(h)
    assert (await m.read_block(h)).decompressed() == data
    await shutdown(systems)


async def test_resync_fetches_missing_block(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(80_000)
    h = blake2s_sum(data)
    await managers[0].rpc_put_block(h, data)
    await asyncio.sleep(0.1)
    victim = managers[1]
    # mark needed (rc>0) then delete local file → resync must re-fetch
    victim.db.transaction(lambda tx: victim.rc.block_incref(tx, h))
    found = victim.find_block(h)
    if found:
        os.remove(found[0])
    assert await victim.need_block(h)
    await victim.resync.resync_block(h)
    assert victim.is_block_present(h)
    assert (await victim.rpc_get_block(h)) == data
    await shutdown(systems)


async def test_resync_offloads_and_deletes_unneeded(tmp_path):
    systems, managers = await make_block_cluster(tmp_path)
    data = os.urandom(60_000)
    h = blake2s_sum(data)
    # only node 0 has the block; rc=0 there (deletable immediately)
    await managers[0].write_block(h, DataBlock.plain(data))
    import garage_tpu.block.rc as rc_mod

    # force the deletion timer into the past
    managers[0].rc.tree.insert(
        bytes(h), rc_mod.pack([0, 1])
    )
    # other replicas need it: rc>0, no file
    for m in managers[1:]:
        m.db.transaction(lambda tx, m=m: m.rc.block_incref(tx, h))
    await managers[0].resync.resync_block(h)
    await asyncio.sleep(0.1)
    assert not managers[0].is_block_present(h)  # deleted locally
    for m in managers[1:]:
        if bytes(m.system.id) in [bytes(x) for x in managers[0].replication.write_nodes(h)]:
            assert m.is_block_present(h)
    await shutdown(systems)


async def test_drain_push_moves_block_before_refs_migrate(tmp_path):
    """A layout change can un-assign a node while its refs are still
    live (table sync lags the ring).  The draining holder must push to
    the new owners immediately — need_block's drain flag lets them
    accept on ring assignment alone — and must NOT drop its local copy
    while rc is nonzero (deletion belongs to the migrating branch once
    the refs leave)."""
    systems, managers = await make_block_cluster(tmp_path, n=4)
    data = os.urandom(70_000)
    h = blake2s_sum(data)
    owners = [bytes(x) for x in managers[0].replication.write_nodes(h)]
    victim = next(m for m in managers if bytes(m.system.id) not in owners)
    # the un-assigned node holds the block and still references it:
    # exactly the post-drain state before table sync migrates the refs
    await victim.write_block(h, DataBlock.plain(data))
    victim.db.transaction(lambda tx: victim.rc.block_incref(tx, h))
    assert not victim.is_assigned(h)
    holders = [m for m in managers if bytes(m.system.id) in owners]
    for m in holders:
        # their rc is as stale as the victim's assignment: without the
        # drain flag nobody would accept and the drain's bytes would
        # wait on metadata migration
        assert not await m.need_block(h)
        assert await m.need_block(h, drain=True)
    await victim.resync.resync_block(h)
    for m in holders:
        assert m.is_block_present(h)
    # refs still live → the local copy survives the push
    assert victim.is_block_present(h)
    await shutdown(systems)


# --- scrub ---


async def test_scrub_batch_detects_corruption(tmp_path):
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    datas = [os.urandom(30_000) for _ in range(20)]
    hashes = [blake2s_sum(d) for d in datas]
    for h, d in zip(hashes, datas):
        await m.write_block(h, DataBlock.plain(d))
    # corrupt 3 of them on disk
    for h in hashes[:3]:
        path, _ = m.find_block(h)
        with open(path, "r+b") as f:
            f.seek(10)
            f.write(b"\x00\x01\x02\x03")
    scrub = ScrubWorker(m)
    scrub.send_command("start")
    while (await scrub.work()).name in ("BUSY", "THROTTLED"):
        pass
    assert scrub.state.corruptions == 3
    assert m.resync.queue_len() == 3
    present = sum(1 for h in hashes if m.is_block_present(h))
    assert present == 17
    await shutdown(systems)


async def test_scrub_checkpoint_and_resume(tmp_path):
    """Kill mid-scrub (drop the worker), restart from the same persister:
    the new worker resumes running from the checkpointed position
    (ref repair.rs:185-229 persisted scrub state)."""
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    for _ in range(40):
        d = os.urandom(5_000)
        await m.write_block(blake2s_sum(d), DataBlock.plain(d))
    pers = Persister(str(tmp_path / "meta"), "scrub_info", ScrubWorkerState)
    w = ScrubWorker(m, persister=pers)
    w.send_command("start")
    await w.work()   # applies start (checkpoints), scrubs the first prefix
    w._checkpoint(force=True)
    # persisted position = VERIFIED position; the iterator runs one
    # prefix ahead (read-ahead), so resume re-verifies the in-flight one
    pos = w.state.position
    assert w.state.running and pos > 0
    assert w.iterator.position >= pos
    w._drop_read_ahead()

    # "kill -9": drop w without any shutdown; restart from disk
    w2 = ScrubWorker(m, persister=pers)
    assert w2.state.running
    assert w2.iterator is not None and w2.iterator.position == pos
    while (await w2.work()).name in ("BUSY", "THROTTLED"):
        pass
    assert not w2.state.running and w2.state.time_last_complete > 0
    # completion checkpointed: a third restart schedules the next run
    w3 = ScrubWorker(m, persister=pers)
    assert not w3.state.running and w3.iterator is None
    assert w3.state.time_next_run > 0
    await shutdown(systems)


async def test_resync_config_persists(tmp_path):
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    pers = Persister(str(tmp_path / "meta"), "resync_cfg", ResyncPersistedConfig)
    r = BlockResyncManager(m, open_db("memory"), persister=pers)
    r.set_n_workers(4)
    r.set_tranquility(7)
    with pytest.raises(ValueError):
        r.set_n_workers(0)
    with pytest.raises(ValueError):
        r.set_n_workers(99)
    r2 = BlockResyncManager(m, open_db("memory"), persister=pers)
    assert r2.n_workers == 4 and r2.tranquility == 7
    await shutdown(systems)


async def test_block_store_iterator_resumable(tmp_path):
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    hashes = []
    for _ in range(30):
        d = os.urandom(1000)
        h = blake2s_sum(d)
        hashes.append(h)
        await m.write_block(h, DataBlock.plain(d))
    roots = [dd.path for dd in m.data_layout.data_dirs]
    it = BlockStoreIterator(roots)
    seen = []
    # stop midway, then resume from the persisted position
    while len(seen) < 15:
        batch = it.next_prefix()
        assert batch is not None
        seen.extend(h for h, _p, _c in batch)
    it2 = BlockStoreIterator(roots, position=it.position)
    while (batch := it2.next_prefix()) is not None:
        seen.extend(h for h, _p, _c in batch)
    assert sorted(bytes(h) for h in seen) == sorted(bytes(h) for h in hashes)
    await shutdown(systems)


async def test_streaming_get_midstream_failover(tmp_path):
    """A node dying mid-stream must not kill the read: the stream resumes
    on the next replica, skipping already-delivered bytes (ref
    manager.rs:231-345; VERDICT r1 weak #5)."""
    for payload in (os.urandom(600_000), b"A" * 600_000):  # plain + zstd
        systems, managers = await make_block_cluster(tmp_path / str(len(payload)))
        h = blake2s_sum(payload)
        await managers[0].rpc_put_block(h, payload)
        await asyncio.sleep(0.2)
        assert sum(1 for m in managers if m.is_block_present(h)) == 3

        # poison node0 (= self, first in request_order): its get_block
        # stream dies after ~200 KB on the wire
        m0 = managers[0]
        orig = m0._handle

        async def poison(remote, msg, body, _orig=orig):
            resp, stream = await _orig(remote, msg, body)
            if msg.get("t") == "get_block" and stream is not None:
                async def dying(_s=stream):
                    sent = 0
                    async for c in _s:
                        yield c
                        sent += len(c)
                        if sent >= 200_000:
                            raise RuntimeError("simulated node crash")
                return resp, dying()
            return resp, stream

        m0.endpoint.set_handler(poison)
        got = bytearray()
        async for chunk in m0.rpc_get_block_streaming(h):
            got.extend(chunk)
        assert bytes(got) == payload

        # the RAW fetch path (resync/repair) rides the SAME failover:
        # a storable DataBlock comes back whole despite the mid-stream
        # death, re-compressed when that pays (for_storage)
        block = await m0.rpc_get_raw_block(h, for_storage=True)
        assert block.decompressed() == payload
        assert bytes(blake2s_sum(block.decompressed())) == bytes(h)
        await shutdown(systems)


async def test_scrub_with_hybrid_codec(tmp_path):
    """The production scrub worker runs with codec backend='hybrid'
    (config-selected): corruption detection works identically whichever
    side of the link gate runs a batch (the device backend is the JAX
    CPU platform here)."""
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    from garage_tpu.ops import make_codec

    m.codec = make_codec("hybrid", rs_data=4, rs_parity=2)
    datas = [os.urandom(20_000) for _ in range(24)]
    hashes = [blake2s_sum(d) for d in datas]
    for h, d in zip(hashes, datas):
        await m.write_block(h, DataBlock.plain(d))
    for h in hashes[:2]:
        path, _ = m.find_block(h)
        with open(path, "r+b") as f:
            f.seek(5)
            f.write(b"\xba\xad")
    scrub = ScrubWorker(m)
    scrub.send_command("start")
    while (await scrub.work()).name in ("BUSY", "THROTTLED"):
        pass
    assert scrub.state.corruptions == 2
    assert sum(1 for h in hashes if m.is_block_present(h)) == 22
    await shutdown(systems)


async def test_rebalance_moves_blocks_to_primary_dir(tmp_path):
    """Multi-drive rebalance (ref repair.rs:531-626): after a drive-layout
    change, RebalanceWorker moves each block file into its new primary
    directory and the manager still finds/reads every block."""
    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    hashes = []
    for _ in range(24):
        d = os.urandom(2000)
        h = blake2s_sum(d)
        hashes.append((h, d))
        await m.write_block(h, DataBlock.plain(d))

    # add a second, much larger drive: most partitions move primary
    d1 = m.data_layout.data_dirs[0].path
    d2 = str(tmp_path / "drive2")
    new_dirs = [{"path": d1, "capacity": 100},
                {"path": d2, "capacity": 900}]
    m.data_layout = m.data_layout.update(new_dirs)
    os.makedirs(d2, exist_ok=True)

    moved_expected = [
        h for h, _d in hashes
        if m.data_layout.primary_dir(h) != d1
    ]
    assert moved_expected, "bigger drive must take over some partitions"

    w = RebalanceWorker(m)
    while (await w.work()).name != "DONE":
        pass
    assert w.moved >= len(moved_expected)

    for h, d in hashes:
        path, compressed = m.find_block(h)
        assert path.startswith(m.data_layout.primary_dir(h))
        assert not compressed
        block = await m.read_block(h)
        assert block.inner == d
    await shutdown(systems)


async def test_parity_sidecar_local_reconstruction(tmp_path):
    """RS decode-repair with every replica unreachable (BASELINE config
    #4): scrub persists parity sidecars; a corrupted block is rebuilt
    LOCALLY from its codeword's surviving pieces — zero network — and a
    lost block resyncs from parity before trying the (dead) replicas."""
    from garage_tpu.block.parity import ParityStore
    from garage_tpu.block.repair import ScrubWorker

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    m.blocks_reconstructed = 0
    db = open_db("memory")
    m.parity_store = ParityStore(m, db, m.codec)

    # 16 blocks = 2 full RS(8,4) codewords, varying sizes; one of them
    # stored COMPRESSED (must be covered by parity too)
    blocks = {}
    for i in range(16):
        if i == 3:
            d = b"compressible " * 900 + os.urandom(50)
        else:
            d = os.urandom(9000 + 137 * i)
        h = blake2s_sum(d)
        blocks[bytes(h)] = d
        await m.write_block(h, DataBlock.from_buffer(d, 3))
    assert any(c for _p, c in map(m.find_block, map(Hash, blocks))), \
        "expected at least one compressed block"

    # scrub pass persists the parity sidecars
    w = ScrubWorker(m)
    w.send_command("start")
    while (await w.work()).name in ("BUSY", "THROTTLED"):
        pass
    assert m.parity_store.stats()["indexed_blocks"] == 16
    assert w.state.corruptions == 0

    # corrupt one block on disk; scrub detects it and repairs it from
    # LOCAL parity (no resync entry — the network path was never needed)
    victim = next(iter(blocks))
    vh = Hash(victim)
    path, _ = m.find_block(vh)
    data = bytearray(blocks[victim])
    data[100] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))

    w2 = ScrubWorker(m)
    w2.send_command("start")
    while (await w2.work()).name in ("BUSY", "THROTTLED"):
        pass
    assert w2.state.corruptions == 1
    assert m.blocks_reconstructed == 1
    assert m.resync.queue_len() == 0, "local repair must not hit the network"
    block = await m.read_block(vh)
    assert block.inner == blocks[victim]

    # a DELETED block also reconstructs via the resync path, again with
    # zero replicas available (single-node cluster: there are none)
    victim2 = list(blocks)[5]
    vh2 = Hash(victim2)
    path2, _ = m.find_block(vh2)
    os.remove(path2)
    rebuilt = m.parity_store.try_reconstruct(vh2)
    assert rebuilt == blocks[victim2]

    # churn + GC: remove a block of the OTHER codeword, so neither can
    # be whole again.  The pass that misses them dissolves both (their
    # survivors keep the old sidecars as cover), the next regroups the
    # first k survivors in id order, and the old sidecar under which no
    # survivor waits is purged once the purge's grace is over, its index
    # entries pruned; the other stays: it is six blocks' only cover
    import time as _time

    order = sorted(blocks)
    removed_h = order[12] if order.index(victim2) < 8 else order[3]
    lost = sorted((victim2, removed_h))     # [of order[:8], of order[8:]]
    rf = m.find_block(Hash(removed_h))
    os.remove(rf[0])
    _time.sleep(0.05)
    for _pass in range(4):
        w2.send_command("start")
        while (await w2.work()).name in ("BUSY", "THROTTLED"):
            pass
        _time.sleep(0.05)
    files_after = sum(
        len(fs) for _d, _s, fs in os.walk(m.parity_store.dir))
    assert files_after == 2, "orphaned sidecar never purged"
    # 14 surviving blocks = 1 full codeword and 6 under the old one,
    # which names the block it lost too
    assert m.parity_store.stats()["indexed_blocks"] == 15
    assert not m.parity_store.coverage(Hash(lost[0]))
    assert all(m.parity_store.coverage(Hash(h)) for h in order
               if h != lost[0])

    # fewer than k surviving pieces → reconstruction refuses
    for i, hb in enumerate(list(blocks)):
        if hb in (victim, victim2):
            continue
        found = m.find_block(Hash(hb))
        if found:
            os.remove(found[0])
    assert m.parity_store.try_reconstruct(vh2) is None
    await shutdown(systems)


async def test_write_time_parity(tmp_path):
    """BASELINE config #3: parity exists from FIRST WRITE (no scrub pass
    needed).  Full codewords flush at k blocks; a partial codeword
    (object smaller than k blocks) flushes on drain and reconstructs
    against implicit zero shards."""
    from garage_tpu.block.parity import ParityStore, WriteParityAccumulator

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    m.blocks_reconstructed = 0
    db = open_db("memory")
    m.parity_store = ParityStore(m, db, m.codec)
    m.write_parity = WriteParityAccumulator(m.parity_store, m.codec,
                                            flush_after=0.2)

    k = m.codec.params.rs_data
    # one full codeword: k blocks, varying sizes, one compressible
    datas = [os.urandom(8000 + 321 * i) for i in range(k - 1)]
    datas.append(b"compressible " * 700)
    hs = [blake2s_sum(d) for d in datas]
    for h, d in zip(hs, datas):
        await m.write_block(h, DataBlock.from_buffer(d, 3))
    # k-th write triggers the flush; encode runs async — wait for it,
    # the accumulator's own flush and not the clock: six busy workers
    # can hold an encode thread back past any poll
    await m.write_parity.settled()
    assert all(m.parity_store.coverage(h) for h in hs), \
        "full codeword must be covered right after the k-th write, no scrub"

    # corrupt one member on disk; read path detects, resync repairs from
    # the WRITE-TIME sidecar (zero network: single-node cluster)
    victim = hs[2]
    path, _ = m.find_block(victim)
    raw = bytearray(open(path, "rb").read())
    raw[50] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    assert m.parity_store.try_reconstruct(victim) == datas[2]

    # partial codeword: 2 more blocks (< k), flushed by the timer
    small = [os.urandom(5000), os.urandom(6000)]
    sh = [blake2s_sum(d) for d in small]
    for h, d in zip(sh, small):
        await m.write_block(h, DataBlock.plain(d))
    await m.write_parity.settled()
    assert m.parity_store.coverage(sh[0]) and m.parity_store.coverage(sh[1])
    # delete one member: reconstruction uses the survivor + zero shards
    p2, _ = m.find_block(sh[1])
    os.remove(p2)
    assert m.parity_store.try_reconstruct(sh[1]) == small[1]

    # dedupe: re-writing an existing block must not enter a new codeword
    before = len(m.write_parity._pending)
    await m.write_block(hs[0], DataBlock.from_buffer(datas[0], 3))
    assert len(m.write_parity._pending) == before
    await shutdown(systems)


async def test_write_time_parity_drain_flushes_tail(tmp_path):
    """Shutdown with a partial codeword pending: drain() must flush and
    persist it (clean stop loses nothing)."""
    from garage_tpu.block.parity import ParityStore, WriteParityAccumulator

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    db = open_db("memory")
    m.parity_store = ParityStore(m, db, m.codec)
    m.write_parity = WriteParityAccumulator(m.parity_store, m.codec,
                                            flush_after=60.0)  # never fires
    d = os.urandom(7000)
    h = blake2s_sum(d)
    await m.write_block(h, DataBlock.plain(d))
    assert not m.parity_store.coverage(h)
    await m.write_parity.drain()
    assert m.parity_store.coverage(h)
    await shutdown(systems)


async def test_parity_geometry_change_recovers_coverage(tmp_path):
    """Regression: the sidecar group id must include the (k, m) codec
    geometry.  With member-hashes-only gids, changing rs_parity made
    put_codeword mtime-touch the OLD-geometry file every pass (so the
    purge never removed it) while _load_manifest rejected it on its
    (k, m) check — local-repair coverage was silently and permanently
    lost for the codeword."""
    from garage_tpu.block.parity import ParityStore
    from garage_tpu.ops import make_codec

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    db = open_db("memory")
    m.codec = make_codec("cpu", rs_data=4, rs_parity=2, batch_blocks=64)
    store = ParityStore(m, db, m.codec)

    datas = [os.urandom(5000 + i) for i in range(4)]
    hs = [blake2s_sum(d) for d in datas]
    for h, d in zip(hs, datas):
        await m.write_block(h, DataBlock.plain(d))
    parity = m.codec.rs_encode_blocks(datas)[0]
    store.put_codeword(hs, [len(d) for d in datas], parity)
    assert store.coverage(hs[0])

    # operator changes rs_parity 2 → 3; same members re-encode
    m.codec = make_codec("cpu", rs_data=4, rs_parity=3, batch_blocks=64)
    store2 = ParityStore(m, db, m.codec)
    assert not store2.coverage(hs[0]), "old-geometry sidecar must not count"
    parity3 = m.codec.rs_encode_blocks(datas)[0]
    store2.put_codeword(hs, [len(d) for d in datas], parity3)
    # the new-geometry sidecar must be a NEW file (not a touch of the old
    # one), loadable, and able to reconstruct
    assert store2.coverage(hs[0])
    found = m.find_block(hs[1])
    os.remove(found[0])
    assert store2.try_reconstruct(hs[1]) == datas[1]
    await shutdown(systems)


async def test_resync_prefers_local_parity_over_network(tmp_path):
    """The resync missing-block path reconstructs from the local parity
    sidecar BEFORE trying any replica — on a 1-node cluster there are no
    replicas at all, so success proves zero network was needed."""
    from garage_tpu.block.parity import ParityStore
    from garage_tpu.block.repair import ScrubWorker

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    m.blocks_reconstructed = 0
    m.parity_store = ParityStore(m, open_db("memory"), m.codec)

    datas = [os.urandom(12_000 + i) for i in range(8)]  # one full codeword
    hs = [blake2s_sum(d) for d in datas]
    for h, d in zip(hs, datas):
        await m.write_block(h, DataBlock.plain(d))
    w = ScrubWorker(m)
    w.send_command("start")
    while (await w.work()).name in ("BUSY", "THROTTLED"):
        pass
    assert m.parity_store.stats()["indexed_blocks"] == 8

    # rc>0 + file gone → resync_block must restore it locally
    victim = hs[3]
    m.db.transaction(lambda tx: m.rc.block_incref(tx, victim))
    os.remove(m.find_block(victim)[0])
    assert await m.need_block(victim)
    await m.resync.resync_block(victim)
    assert m.is_block_present(victim)
    assert (await m.read_block(victim)).inner == datas[3]
    assert m.blocks_reconstructed == 1
    await shutdown(systems)

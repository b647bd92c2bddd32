"""Model layer tests: object version CRDT semantics, the
object→version→block_ref→rc hook chain on a real 3-node loopback cluster,
bucket/key/alias helpers, and index counters (SURVEY.md §2.6)."""

import asyncio

import pytest

from garage_tpu.model import Bucket, BucketKeyPerm, Garage, Key
from garage_tpu.model.s3.object_table import (
    BYTES,
    OBJECTS,
    UNFINISHED_UPLOADS,
    Object,
    ObjectVersion,
    ObjectVersionData,
    ObjectVersionHeaders,
    ObjectVersionMeta,
)
from garage_tpu.model.s3.version_table import Version
from garage_tpu.utils.config import config_from_dict
from garage_tpu.utils.data import Hash, blake2s_sum, gen_uuid

pytestmark = pytest.mark.asyncio


def mkconfig(tmp_path, i, mode="3"):
    return config_from_dict({
        "metadata_dir": str(tmp_path / f"n{i}" / "meta"),
        "data_dir": str(tmp_path / f"n{i}" / "data"),
        "replication_mode": mode,
        "rpc_bind_addr": "127.0.0.1:0",
        "rpc_secret": "model-test",
        "db_engine": "memory",
        "bootstrap_peers": [],
    })


async def make_garage_cluster(tmp_path, n=3, mode="3"):
    from garage_tpu.rpc.layout import ClusterLayout, NodeRole

    garages = []
    for i in range(n):
        g = Garage(mkconfig(tmp_path, i, mode))
        await g.system.netapp.listen("127.0.0.1:0")
        garages.append(g)
    ports = [
        g.system.netapp._server.sockets[0].getsockname()[1] for g in garages
    ]
    for i, a in enumerate(garages):
        for j, b in enumerate(garages):
            if i < j:
                await a.system.netapp.connect(
                    f"127.0.0.1:{ports[j]}", expected_id=b.system.id
                )
        a.config.rpc_public_addr = f"127.0.0.1:{ports[i]}"
    lay = garages[0].system.layout
    for g in garages:
        lay.stage_role(bytes(g.system.id), NodeRole("dc1", 1000))
    lay.apply_staged_changes()
    enc = lay.encode()
    for g in garages:
        g.system.layout = ClusterLayout.decode(enc)
        g.system._rebuild_ring()
        assert g.system.ring.ready
    return garages


async def shutdown(garages):
    for g in garages:
        await g.shutdown()


def complete_version(uuid, ts, data: bytes):
    h = ObjectVersionHeaders.new()
    meta = ObjectVersionMeta.new(h, len(data), "etag")
    return ObjectVersion(uuid, ts, ["complete", ObjectVersionData.inline(meta, data)])


# --- pure CRDT tests -------------------------------------------------------


def test_object_merge_prunes_old_versions():
    b = gen_uuid()
    u1, u2, u3 = gen_uuid(), gen_uuid(), gen_uuid()
    o1 = Object(b, "k", [complete_version(u1, 100, b"a")])
    o2 = Object(b, "k", [complete_version(u2, 200, b"bb")])
    o1.merge(o2)
    # only the newest complete version survives
    assert [v.uuid for v in o1.versions()] == [u2]
    # an uploading version newer than the complete one is kept
    up = ObjectVersion.uploading(u3, 300, False, ObjectVersionHeaders.new())
    o3 = Object(b, "k", [up])
    o1.merge(o3)
    assert [v.timestamp for v in o1.versions()] == [200, 300]
    # aborting the upload, then merging, drops it after a newer complete
    o1.versions()[1].merge_state(ObjectVersion(u3, 300, ["aborted"]))
    assert o1.versions()[1].is_aborted()


def test_object_merge_commutative():
    b = gen_uuid()
    u1, u2 = gen_uuid(), gen_uuid()
    v1, v2 = complete_version(u1, 100, b"a"), complete_version(u2, 200, b"bb")
    x = Object(b, "k", [ObjectVersion(v1.uuid, v1.timestamp, list(v1.state))])
    x.merge(Object(b, "k", [v2]))
    y = Object(b, "k", [ObjectVersion(v2.uuid, v2.timestamp, list(v2.state))])
    y.merge(Object(b, "k", [v1]))
    assert x.encode() == y.encode()


def test_object_roundtrip():
    b = gen_uuid()
    o = Object(b, "some/key", [complete_version(gen_uuid(), 42, b"xyz")])
    o2 = Object.decode(o.encode())
    assert o2.encode() == o.encode()
    assert o2.key == "some/key"
    assert o2.last_complete_version().size() == 3


def test_version_merge_deleted_clears_blocks():
    u = gen_uuid()
    v = Version.new(u, b"\x01" * 32, "k")
    v.add_block(1, 0, b"\xaa" * 32, 1000)
    v.add_block(1, 1000, b"\xbb" * 32, 500)
    assert v.total_size() == 1500
    vd = Version.new(u, b"\x01" * 32, "k", deleted=True)
    v.merge(vd)
    assert v.deleted.value and v.blocks == {}
    # commutativity: deleted absorbs concurrent adds
    v2 = Version.new(u, b"\x01" * 32, "k", deleted=True)
    va = Version.new(u, b"\x01" * 32, "k")
    va.add_block(1, 0, b"\xcc" * 32, 10)
    v2.merge(va)
    assert v2.blocks == {}


def test_bucket_key_perm_merge():
    a = BucketKeyPerm(True, False, False, timestamp=10)
    b = BucketKeyPerm(False, True, False, timestamp=20)
    a.merge(b)
    assert (a.allow_read, a.allow_write) == (False, True)
    c = BucketKeyPerm(True, False, False, timestamp=20)
    a.merge(c)  # equal ts → or-merge
    assert (a.allow_read, a.allow_write) == (True, True)


# --- cluster tests ---------------------------------------------------------


async def test_hook_chain_incref_decref(tmp_path):
    """PutObject-like flow: version with blocks → block_refs created →
    rc incremented; object deletion → version tombstone → refs deleted →
    rc decremented (ref SURVEY.md §3.2 hook chain)."""
    garages = await make_garage_cluster(tmp_path)
    g = garages[0]
    for x in garages:
        x.spawn_workers()

    bucket_id = gen_uuid()
    data = b"some block data"
    bh = blake2s_sum(data)

    # simulate the put path: version row with one block
    vu = gen_uuid()
    ver = Version.new(vu, bytes(bucket_id), "obj1")
    ver.add_block(0, 0, bytes(bh), len(data))
    await g.version_table.insert(ver)

    obj = Object(bucket_id, "obj1", [complete_version(vu, 100, b"inline")])
    await g.object_table.insert(obj)

    # wait for insert-queue propagation: block_ref rows + rc increments
    async def rc_positive():
        for _ in range(80):
            n = sum(
                1 for x in garages if x.block_manager.rc.get(Hash(bh)).is_needed()
            )
            if n >= 2:
                return n
            await asyncio.sleep(0.05)
        return 0

    n = await rc_positive()
    assert n >= 2, "block_ref hook should incref on replicas"

    # deletion in S3 = a newer complete version; the merge prunes vu out
    # of the row, the object hook tombstones it in the version table
    del_marker = Object(
        bucket_id, "obj1", [complete_version(gen_uuid(), 200, b"")]
    )
    await g.object_table.insert(del_marker)

    async def rc_zero():
        for _ in range(100):
            n = sum(
                1
                for x in garages
                if not x.block_manager.rc.get(Hash(bh)).is_needed()
            )
            if n == 3:
                return True
            await asyncio.sleep(0.05)
        return False

    assert await rc_zero(), "pruned version should cascade to rc decrement"
    await shutdown(garages)


async def test_bucket_key_helpers(tmp_path):
    garages = await make_garage_cluster(tmp_path)
    g = garages[0]
    h = g.helper()

    bucket = await h.create_bucket("my-bucket")
    key = await h.create_key("test-key")
    await h.set_bucket_key_permissions(
        bucket.id, key.key_id, BucketKeyPerm(True, True, False)
    )

    # resolution from another node (full-copy tables converge via quorum
    # writes — all nodes wrote synchronously here)
    await asyncio.sleep(0.1)
    h2 = garages[1].helper()
    bid = await h2.resolve_bucket("my-bucket")
    assert bytes(bid) == bytes(bucket.id)
    k2 = await h2.get_existing_key(key.key_id)
    assert k2.allow_read(bid) and k2.allow_write(bid) and not k2.allow_owner(bid)
    assert k2.params().secret_key == key.params().secret_key

    # duplicate create refused
    from garage_tpu.model.helper import BucketAlreadyExists

    try:
        await h2.create_bucket("my-bucket")
        assert False, "should have raised"
    except BucketAlreadyExists:
        pass

    # delete bucket: alias gone, key grant revoked
    await h.delete_bucket(bucket.id)
    await asyncio.sleep(0.1)
    assert await h2.resolve_global_bucket_name("my-bucket") is None
    k3 = await h2.get_existing_key(key.key_id)
    assert not k3.allow_read(bid)
    await shutdown(garages)


async def test_mpu_abort_cascade(tmp_path):
    """Pruning a multipart-uploading version tombstones the MPU row, whose
    hook tombstones every part version, cascading to block refs."""
    garages = await make_garage_cluster(tmp_path)
    for x in garages:
        x.spawn_workers()
    g = garages[0]
    from garage_tpu.model.s3.mpu_table import MultipartUpload, MpuPart
    from garage_tpu.utils.crdt import now_msec

    bucket_id = gen_uuid()
    upload_id = gen_uuid()
    part_version = gen_uuid()
    bh = blake2s_sum(b"part data")

    mpu = MultipartUpload(upload_id, 100, bytes(bucket_id), "big", parts={
        (1, 100): MpuPart.new(bytes(part_version), "pe1", 9),
    })
    await g.mpu_table.insert(mpu)
    pv = Version(part_version, bytes(bucket_id), "big",
                 mpu_upload_id=bytes(upload_id))
    pv.add_block(1, 0, bytes(bh), 9)
    await g.version_table.insert(pv)
    obj = Object(bucket_id, "big", [
        ObjectVersion.uploading(upload_id, 100, True, ObjectVersionHeaders.new())
    ])
    await g.object_table.insert(obj)

    for _ in range(100):
        if any(x.block_manager.rc.get(Hash(bytes(bh))).is_needed() for x in garages):
            break
        await asyncio.sleep(0.05)

    # completing a newer plain version prunes the uploading MPU version
    done = Object(bucket_id, "big", [complete_version(gen_uuid(), 200, b"zz")])
    await g.object_table.insert(done)

    ok = False
    for _ in range(200):
        refs_dead = all(
            not x.block_manager.rc.get(Hash(bytes(bh))).is_needed()
            for x in garages
        )
        m = await g.mpu_table.get(upload_id, "")
        v = await g.version_table.get(part_version, "")
        if refs_dead and (m is None or m.deleted.value) and (v is None or v.deleted.value):
            ok = True
            break
        await asyncio.sleep(0.05)
    assert ok, "MPU abort cascade did not complete"
    await shutdown(garages)


async def test_object_counters(tmp_path):
    garages = await make_garage_cluster(tmp_path)
    for x in garages:
        x.spawn_workers()
    g = garages[0]
    bucket_id = gen_uuid()

    for i in range(3):
        obj = Object(
            bucket_id, f"obj{i}", [complete_version(gen_uuid(), 100, b"x" * 10)]
        )
        await g.object_table.insert(obj)

    async def totals():
        for _ in range(100):
            t = await g.object_counter.get_totals(bytes(bucket_id))
            if t.get(OBJECTS) == 3:
                return t
            await asyncio.sleep(0.05)
        return await g.object_counter.get_totals(bytes(bucket_id))

    t = await totals()
    assert t.get(OBJECTS) == 3
    assert t.get(BYTES) == 30
    assert t.get(UNFINISHED_UPLOADS, 0) == 0
    await shutdown(garages)


async def test_worker_vars_persist_across_restart(tmp_path):
    """`worker set` tunables survive a daemon restart (ref
    block/manager.rs:209-227 + resync.rs:143-173 persisted vars)."""
    garages = await make_garage_cluster(tmp_path)
    g = garages[0]
    g.spawn_workers()
    g.bg_vars.set("resync-worker-count", 4)
    g.bg_vars.set("resync-tranquility", 5)
    g.bg_vars.set("scrub-tranquility", 9)
    assert g.bg_vars.get("resync-worker-count") == 4
    assert g.bg_vars.all()["scrub-tranquility"] == 9
    await shutdown(garages)

    g2 = Garage(mkconfig(tmp_path, 0))
    g2.spawn_workers()
    assert g2.block_resync.n_workers == 4
    assert g2.block_resync.tranquility == 5
    assert g2.scrub_worker.state.tranquility == 9
    await g2.shutdown()


async def test_offline_counter_recount_fixes_drift(tmp_path):
    """Deliberately corrupt a bucket's object counter, then rebuild it with
    offline_recount_all (ref index_counter.rs:252+ + repair/offline.rs)."""
    garages = await make_garage_cluster(tmp_path)
    for x in garages:
        x.spawn_workers()
    g = garages[0]
    bucket_id = gen_uuid()
    for i in range(4):
        await g.object_table.insert(Object(
            bucket_id, f"o{i}", [complete_version(gen_uuid(), 100, b"z" * 25)]
        ))

    async def wait_totals(want_objects):
        for _ in range(100):
            t = await g.object_counter.get_totals(bytes(bucket_id))
            if t.get(OBJECTS) == want_objects:
                return t
            await asyncio.sleep(0.05)
        return await g.object_counter.get_totals(bytes(bucket_id))

    t = await wait_totals(4)
    assert t.get(OBJECTS) == 4 and t.get(BYTES) == 100

    # corrupt: phantom deltas on every node (drifted counters)
    for x in garages:
        x.db.transaction(lambda tx, x=x: x.object_counter.count(
            tx, bytes(bucket_id), "", [], [(OBJECTS, 1000), (BYTES, 1_000_000)]
        ))
    t = await wait_totals(1004)
    assert t.get(OBJECTS) == 1004

    # recount on every node (its own local rows), then wait for the
    # insert-queue propagation to converge
    for x in garages:
        z, n = x.object_counter.offline_recount_all(
            x.object_table, lambda e: (bytes(e.bucket_id), "")
        )
        assert n >= 1
    t = await wait_totals(4)
    assert t.get(OBJECTS) == 4 and t.get(BYTES) == 100
    await shutdown(garages)


async def test_admin_block_ops(tmp_path):
    """Block-level admin ops (ref garage/admin/block.rs): list-errors,
    info (refcount + referencing versions), retry-now, purge."""
    from garage_tpu.admin.handler import AdminRpcHandler

    garages = await make_garage_cluster(tmp_path, n=1, mode="1")
    g = garages[0]
    g.spawn_workers()
    adm = AdminRpcHandler(g, register_endpoint=False)

    bucket_id = gen_uuid()
    data = b"admin block ops payload"
    bh = blake2s_sum(data)
    from garage_tpu.block.block import DataBlock

    await g.block_manager.write_block(Hash(bh), DataBlock.plain(data))
    vu = gen_uuid()
    ver = Version.new(vu, bytes(bucket_id), "purgeme")
    ver.add_block(0, 0, bytes(bh), len(data))
    await g.version_table.insert(ver)
    obj = Object(bucket_id, "purgeme", [complete_version(vu, 100, b"x")])
    await g.object_table.insert(obj)
    # wait for the block_ref hook
    for _ in range(80):
        if g.block_manager.rc.get(Hash(bh)).is_needed():
            break
        await asyncio.sleep(0.05)

    # info: refcount + the referencing version with its backlink
    info = await adm._cmd_block_info({"hash": bytes(bh).hex()})
    assert info["refcount"] == 1 and info["present"]
    assert info["versions"][0]["key"] == "purgeme"

    # error queue: inject one, list it, retry it
    g.block_manager.resync.put_to_resync(Hash(bh), 0.0)
    from garage_tpu.block.resync import ErrorCounter

    g.block_manager.resync.errors.insert(
        bytes(bh), ErrorCounter(3, 1).serialize())
    errs = await adm._cmd_block_list_errors({})
    assert len(errs) == 1 and errs[0]["errors"] == 3
    out = await adm._cmd_block_retry_now({"all": True})
    assert out.startswith("1 blocks")
    assert await adm._cmd_block_list_errors({}) == []

    # purge requires --yes, then tombstones version + writes delete marker
    from garage_tpu.utils.error import GarageError

    with pytest.raises(GarageError, match="--yes"):
        await adm._cmd_block_purge({"blocks": [bytes(bh).hex()]})
    out = await adm._cmd_block_purge(
        {"yes": True, "blocks": [bytes(bh).hex()]})
    assert "1 versions" in out and "1 objects" in out, out
    v2 = await g.version_table.get(vu, "")
    assert v2.deleted.value
    o2 = await g.object_table.get(bucket_id, "purgeme")
    assert o2.last_data_version() is None  # delete marker on top
    await shutdown(garages)


async def test_table_repair_launchers_reap_orphans(tmp_path):
    """repair versions / block_refs / mpu tombstone rows whose parent no
    longer references them (ref repair/online.rs RepairVersions,
    RepairBlockRefs, RepairMpu)."""
    from garage_tpu.admin.handler import AdminRpcHandler
    from garage_tpu.model.s3.block_ref_table import BlockRef
    from garage_tpu.model.s3.mpu_table import MultipartUpload

    garages = await make_garage_cluster(tmp_path, n=1, mode="1")
    g = garages[0]
    g.spawn_workers()
    adm = AdminRpcHandler(g, register_endpoint=False)

    # `repair tables` actually fills every syncer's todo (it was once a
    # silent no-op when spawn_workers bypassed make_worker)
    await adm._cmd_launch_repair({"what": "tables"})
    assert all(t.syncer.worker is not None and t.syncer.worker.todo
               for t in g.tables)

    bucket_id = gen_uuid()
    # orphan version: no object row carries its uuid
    vu = gen_uuid()
    await g.version_table.insert(Version.new(vu, bytes(bucket_id), "ghost"))
    # orphan block_ref: its version uuid does not exist
    bh = blake2s_sum(b"orphan block payload")
    bru = gen_uuid()
    await g.block_ref_table.insert(BlockRef(Hash(bh), bru))
    # orphan mpu: object row has no matching Uploading{multipart} version
    mu = gen_uuid()
    await g.mpu_table.insert(
        MultipartUpload(mu, 1, bytes(bucket_id), "mkey"))
    # live mpu: object row DOES carry the uploading version — must survive
    mu_live = gen_uuid()
    await g.mpu_table.insert(
        MultipartUpload(mu_live, 2, bytes(bucket_id), "live"))
    await g.object_table.insert(Object(bucket_id, "live", [
        ObjectVersion.uploading(mu_live, 2, True, {})
    ]))

    assert await adm._repair_versions() == 1
    assert (await g.version_table.get(vu, "")).deleted.value
    assert await adm._repair_block_refs() == 1
    assert (await g.block_ref_table.get(Hash(bh), bru)).deleted.value
    assert await adm._repair_mpu() == 1
    assert (await g.mpu_table.get(mu, "")).deleted.value
    assert not (await g.mpu_table.get(mu_live, "")).deleted.value
    # idempotent: a second pass finds nothing
    assert await adm._repair_versions() == 0
    assert await adm._repair_mpu() == 0
    await shutdown(garages)


async def test_layout_change_migrates_data(tmp_path):
    """Cluster elasticity end-to-end (ref staged layout changes +
    TableSyncer offload + block_ref hook chain; the reference's
    test-renumbering scenario): add a node -> anti-entropy populates its
    tables and the ref-count hooks pull the block payloads it now owns;
    remove a node -> its partitions offload and data stays readable."""
    import os as _os

    from garage_tpu.rpc.layout import ClusterLayout, NodeRole

    garages = await make_garage_cluster(tmp_path, n=3, mode="3")
    for g in garages:
        g.spawn_workers()

    # seed: 8 objects in 8 distinct buckets (distinct partitions), each
    # with one 5 KiB block
    buckets = {}
    blocks = {}
    for i in range(8):
        bucket_id = gen_uuid()
        data = _os.urandom(5000)
        bh = blake2s_sum(data)
        await garages[0].block_manager.rpc_put_block(Hash(bh), data)
        vu = gen_uuid()
        ver = Version.new(vu, bytes(bucket_id), f"obj{i}")
        ver.add_block(0, 0, bytes(bh), len(data))
        await garages[0].version_table.insert(ver)
        await garages[0].object_table.insert(
            Object(bucket_id, f"obj{i}", [complete_version(vu, 100 + i, b"x")]))
        buckets[f"obj{i}"] = bucket_id
        blocks[f"obj{i}"] = bh

    # --- grow: node 3 joins ------------------------------------------------
    g3 = Garage(mkconfig(tmp_path, 3))
    await g3.system.netapp.listen("127.0.0.1:0")
    port3 = g3.system.netapp._server.sockets[0].getsockname()[1]
    for g in garages:
        await g.system.netapp.connect(f"127.0.0.1:{port3}",
                                      expected_id=g3.system.id)
    g3.spawn_workers()
    garages.append(g3)

    lay = ClusterLayout.decode(garages[0].system.layout.encode())
    lay.stage_role(bytes(g3.system.id), NodeRole("dc1", 1000))
    lay.apply_staged_changes()
    enc = lay.encode()
    for g in garages:
        g.system.layout = ClusterLayout.decode(enc)
        g.system._rebuild_ring()  # fires on_ring_change -> full syncs

    ring = g3.system.ring

    from garage_tpu.table.schema import hash_partition_key

    def g3_owns(h) -> bool:
        return bytes(g3.system.id) in [
            bytes(n) for n in ring.get_nodes(h, 3)
        ]

    want_rows = [k for k, b in buckets.items()
                 if g3_owns(hash_partition_key(b))]
    want_blocks = [k for k, bh in blocks.items() if g3_owns(Hash(bh))]
    assert want_rows and want_blocks, "new node owns nothing?! (ring bug)"
    # anti-entropy must copy the table rows; the block_ref updated() hook
    # on g3 increfs and resync fetches the payloads it now owns
    for _ in range(200):
        have_rows = sum(
            1 for k in want_rows
            if any(g3.object_table.data.decode_entry(raw).key == k
                   for _x, raw in g3.object_table.data.store.items(b"", None))
        )
        have_blocks = sum(
            1 for k in want_blocks
            if g3.block_manager.is_block_present(Hash(blocks[k]))
        )
        if have_rows == len(want_rows) and have_blocks == len(want_blocks):
            break
        await asyncio.sleep(0.25)
    assert have_rows == len(want_rows), \
        f"{have_rows}/{len(want_rows)} rows on new node"
    assert have_blocks == len(want_blocks), \
        f"{have_blocks}/{len(want_blocks)} blocks on new node"

    # --- shrink: node 0 leaves --------------------------------------------
    g0 = garages[0]
    lay = ClusterLayout.decode(g0.system.layout.encode())
    lay.stage_role(bytes(g0.system.id), None)
    lay.apply_staged_changes()
    enc = lay.encode()
    for g in garages:
        g.system.layout = ClusterLayout.decode(enc)
        g.system._rebuild_ring()

    # node0's syncer offloads partitions it no longer owns: its local
    # object table empties while the data stays readable cluster-wide
    for _ in range(200):
        left = len(list(g0.object_table.data.store.items(b"", None)))
        if left == 0:
            break
        await asyncio.sleep(0.25)
    assert left == 0, f"{left} rows still on removed node"
    for i in range(8):
        obj = await garages[2].object_table.get(
            buckets[f"obj{i}"], f"obj{i}")
        assert obj is not None and obj.last_data_version() is not None
    await shutdown(garages)


async def make_ec_cluster(tmp_path, n, rs=(4, 2), fast_flush=True):
    """n-node erasure-coded cluster: meta "3", data "none", RS(k, m)
    write-time distributed parity.  Shared by the distributed-parity
    tests."""
    from garage_tpu.rpc.layout import ClusterLayout, NodeRole

    garages = []
    for i in range(n):
        garages.append(Garage(config_from_dict({
            "metadata_dir": str(tmp_path / f"n{i}" / "meta"),
            "data_dir": str(tmp_path / f"n{i}" / "data"),
            "replication_mode": "3",
            "data_replication_mode": "none",
            "rpc_bind_addr": "127.0.0.1:0",
            "rpc_secret": "ec-test",
            "db_engine": "memory",
            "bootstrap_peers": [],
            "codec": {
                "rs_data": rs[0], "rs_parity": rs[1],
                "store_parity": True, "parity_on_write": True,
                "parity_distribute": True,
            },
        })))
    for g in garages:
        await g.system.netapp.listen("127.0.0.1:0")
        if fast_flush:
            g.block_manager.ec_accumulator.flush_after = 0.2
    ports = [
        g.system.netapp._server.sockets[0].getsockname()[1] for g in garages
    ]
    for i, a in enumerate(garages):
        for j, b in enumerate(garages):
            if i < j:
                await a.system.netapp.connect(
                    f"127.0.0.1:{ports[j]}", expected_id=b.system.id)
            if i != j:
                # record the ADDRESS both ways: addr-less peer entries
                # evaporate on disconnect, and the peering loop (started
                # below, like a real daemon) can only redial known addrs
                a.system.peering.add_peer(
                    f"127.0.0.1:{ports[j]}", b.system.id)
        a.config.rpc_public_addr = f"127.0.0.1:{ports[i]}"
        a.system.peering.start()
    lay = garages[0].system.layout
    for g in garages:
        lay.stage_role(bytes(g.system.id), NodeRole("dc1", 1000))
    lay.apply_staged_changes()
    enc = lay.encode()
    for g in garages:
        g.system.layout = ClusterLayout.decode(enc)
        g.system._rebuild_ring()
        g.spawn_workers()
    return garages



# --- distributed parity: RS survives NODE loss -----------------------------


async def test_distributed_parity_survives_two_node_failures(tmp_path):
    import os

    """BASELINE config #4, the cluster half: erasure-coded storage class
    (meta replicated "3", data "none" — single copy — plus cross-node
    RS(4,2) parity).  Two nodes die, taking the ONLY copy of a block
    (and possibly other codeword pieces) with them; after the layout
    drops the dead nodes, the new primary reconstructs the block from
    ≥ k surviving cross-node pieces (implicit zero shards of partial
    codewords count for free).  The reference's resync has no recourse
    once every replica is gone (resync.rs:457-468)."""
    from garage_tpu.rpc.layout import ClusterLayout
    from garage_tpu.table.schema import hash_partition_key
    from garage_tpu.utils.data import blake2s_sum

    garages = await make_ec_cluster(tmp_path, 5)

    # one object of 4 blocks, written through node 0 with a version row
    # (block refs → rc); each block lands on ONE node (data factor 1),
    # whose write-time accumulator wraps it into a (possibly partial)
    # RS(4,2) codeword and distributes parity + index cross-node
    datas = [os.urandom(20_000 + 37 * i) for i in range(12)]
    hs = [blake2s_sum(d) for d in datas]
    bucket_id = gen_uuid()
    vu = gen_uuid()
    ver = Version.new(vu, bytes(bucket_id), "ec-obj")
    for off, (h, d) in enumerate(zip(hs, datas)):
        await garages[0].block_manager.rpc_put_block(h, d)
        ver.add_block(0, off, bytes(h), len(d))
    await garages[0].version_table.insert(ver)

    async def entry_for(h):
        ents = await garages[0].parity_index_table.get_range(bytes(h), None)
        live = [e for e in ents if not e.is_tombstone()]
        return live[0] if live else None

    entries = {}
    for _ in range(400):
        entries = {bytes(h): await entry_for(h) for h in hs}
        if all(entries.values()):
            break
        await asyncio.sleep(0.05)
    assert all(entries.values()), "write-time parity never distributed"

    def data_node(bh):
        return bytes(
            garages[0].block_manager.replication.write_nodes(Hash(bh))[0])

    id_to_g = {bytes(g.system.id): g for g in garages}

    # choose a victim member + second casualty so the victim's codeword
    # keeps >= k pieces and its parity-index partition keeps quorum
    choice = None
    for h in hs:
        ent = entries[bytes(h)]
        a_node = data_node(h)
        idx_nodes = {
            bytes(x) for x in
            garages[0].parity_index_table.replication.read_nodes(
                hash_partition_key(bytes(h)))
        }
        for b in garages:
            b_node = bytes(b.system.id)
            if b_node == a_node:
                continue
            dead = {a_node, b_node}
            live_members = sum(
                1 for mh in ent.members
                if bytes(mh) != bytes(h) and data_node(mh) not in dead)
            zeros = ent.k - len(ent.members)
            live_parity = sum(
                1 for ph in ent.parity_hashes if data_node(ph) not in dead)
            idx_dead = sum(1 for x in idx_nodes if x in dead)
            if live_members + zeros + live_parity >= ent.k and idx_dead <= 1:
                choice = (h, a_node, b_node)
                break
        if choice:
            break
    assert choice is not None, "no valid (victim, casualty) pair found"
    victim_h, a_node, b_node = choice

    # kill both nodes (close their transports — calls to them now fail)
    for g in (id_to_g[a_node], id_to_g[b_node]):
        await g.shutdown()
    survivors = [
        g for g in garages if bytes(g.system.id) not in (a_node, b_node)]

    # operators drop the dead nodes from the layout; the ring-change
    # callbacks trigger immediate table re-sync on every survivor, the
    # block_ref rows migrate to the new partition homes, their hooks
    # recreate rc + enqueue resync, and resync falls through replicas
    # (all gone, data factor 1) to DISTRIBUTED parity — fully background
    # self-healing, no manual nudges
    slay = survivors[0].system.layout
    slay.stage_role(a_node, None)
    slay.stage_role(b_node, None)
    slay.apply_staged_changes()
    senc = slay.encode()
    for g in survivors:
        g.system.layout = ClusterLayout.decode(senc)
        g.system._rebuild_ring()

    new_primary_id = bytes(
        survivors[0].block_manager.replication.write_nodes(victim_h)[0])
    np_g = next(
        g for g in survivors if bytes(g.system.id) == new_primary_id)

    # a racing first resync attempt (migration still in flight) lands in
    # the standard 60 s retry backoff; nudge it periodically the way an
    # operator's `block retry-now` does — recovery time then tracks the
    # actual migration, not the backoff schedule
    # normal heal is 5-12 s; the generous ceiling is for shared-tenancy
    # CPU storms where the whole suite runs 2-3x slow
    for i in range(6000):
        if np_g.block_manager.is_block_present(victim_h):
            break
        if i % 30 == 29:
            for g in survivors:
                g.block_resync.clear_backoff(victim_h)
                g.block_resync.put_to_resync(victim_h, 0.0)
        await asyncio.sleep(0.1)
    if not np_g.block_manager.is_block_present(victim_h):
        # ground truth dump: every piece of the victim's codeword vs
        # which live node actually holds its file
        ent = entries[bytes(victim_h)]
        print("victim:", bytes(victim_h).hex()[:12],
              "dead:", a_node.hex()[:8], b_node.hex()[:8])
        for tag, hh in ([("member", m) for m in ent.members]
                        + [("parity", p) for p in ent.parity_hashes]):
            holders = [bytes(g.system.id).hex()[:8] for g in garages
                       if g.block_manager.is_block_present(Hash(hh))]
            exp = data_node(hh).hex()[:8]
            print(f"  {tag} {bytes(hh).hex()[:12]} expected@{exp} "
                  f"holders={holders}")
        print("np_g:", bytes(np_g.system.id).hex()[:8],
              "peer book:", [bytes(k).hex()[:8]
                             for k in np_g.system.peering.peers],
              "conns:", [bytes(k).hex()[:8]
                         for k in np_g.system.netapp.conns])
        _d = await np_g.block_manager.parity_reconstructor(victim_h)
        print("direct reconstruct on np_g:", None if _d is None else len(_d))
        ents_np = await np_g.parity_index_table.get_range(
            bytes(victim_h), None)
        print("np_g index entries:", [(e.is_tombstone(),
              len(e.members), e.k) for e in ents_np])
    assert np_g.block_manager.is_block_present(victim_h), \
        "victim not self-healed from distributed parity"
    got = await np_g.block_manager.read_block(victim_h)
    assert got.decompressed() == datas[hs.index(victim_h)]
    assert np_g.block_manager.blocks_reconstructed >= 1
    await shutdown(survivors)


async def test_distributed_parity_gc_on_member_deletion(tmp_path):
    """Deleting the OBJECT (last live version-ref tombstoned) tombstones
    the members' parity-index rows; the member-0 tombstone releases the
    parity blocks' refcounts so dead codewords reclaim their parity
    storage.  The trigger is the block_ref table's global deletion
    signal — local/migration deletes must never fire it."""
    import os

    from garage_tpu.utils.data import blake2s_sum

    garages = await make_ec_cluster(tmp_path, 3)

    datas = [os.urandom(9000 + i) for i in range(4)]
    hs = [blake2s_sum(d) for d in datas]
    bucket_id = gen_uuid()
    vu = gen_uuid()
    ver = Version.new(vu, bytes(bucket_id), "gc-obj")
    for off, (h, d) in enumerate(zip(hs, datas)):
        await garages[0].block_manager.rpc_put_block(h, d)
        ver.add_block(0, off, bytes(h), len(d))
    await garages[0].version_table.insert(ver)

    async def live_entries(h):
        ents = await garages[0].parity_index_table.get_range(bytes(h), None)
        return [e for e in ents if not e.is_tombstone()]

    for _ in range(300):
        if all([await live_entries(h) for h in hs]):
            break
        await asyncio.sleep(0.05)
    assert all([await live_entries(h) for h in hs])

    # delete the object: version tombstone → version-refs tombstone →
    # the ref-drop trigger sees no live refs → index rows tombstone
    ver_del = Version.new(vu, bytes(bucket_id), "gc-obj", deleted=True)
    await garages[0].version_table.insert(ver_del)
    for _ in range(600):
        gone = [not (await live_entries(h)) for h in hs]
        if all(gone):
            break
        await asyncio.sleep(0.05)
    assert all([not (await live_entries(h)) for h in hs]), \
        "index rows must tombstone after object deletion"
    await shutdown(garages)


async def test_parity_survives_layout_offload(tmp_path):
    """Regression (advisor r3, high): a layout change makes nodes offload
    parity_index partitions they no longer own — table/sync.py offload
    ends in delete_if_equal → updated(old, None), a PHYSICAL removal.
    The index hook must not treat it as logical deletion: doing so queued
    sticky-deleted BlockRefs for every parity shard, decref'ing live
    parity blocks cluster-wide and permanently stripping erasure
    coverage of blocks that still exist."""
    import os

    from garage_tpu.model.parity_index_table import is_parity_ref
    from garage_tpu.rpc.layout import ClusterLayout

    garages = await make_ec_cluster(tmp_path, 5)
    try:
        datas = [os.urandom(18_000 + 53 * i) for i in range(12)]
        hs = [blake2s_sum(d) for d in datas]
        bucket_id = gen_uuid()
        vu = gen_uuid()
        ver = Version.new(vu, bytes(bucket_id), "offload-obj")
        for off, (h, d) in enumerate(zip(hs, datas)):
            await garages[0].block_manager.rpc_put_block(h, d)
            ver.add_block(0, off, bytes(h), len(d))
        await garages[0].version_table.insert(ver)

        async def live_entries(g, h):
            ents = await g.parity_index_table.get_range(bytes(h), None)
            return [e for e in ents if not e.is_tombstone()]

        entries = {}
        for _ in range(400):
            entries = {}
            for h in hs:
                live = await live_entries(garages[0], h)
                if live:
                    entries[bytes(h)] = live[0]
            if len(entries) == len(hs):
                break
            await asyncio.sleep(0.05)
        assert len(entries) == len(hs), "write-time parity never distributed"

        # layout change: the LAST node leaves the cluster; its syncer must
        # offload every partition it held (incl. parity_index rows) and
        # delete them locally — the updated(old, None) storm under test
        leaver = garages[-1]
        lay = ClusterLayout.decode(garages[0].system.layout.encode())
        lay.stage_role(bytes(leaver.system.id), None)
        lay.apply_staged_changes()
        enc = lay.encode()
        for g in garages:
            g.system.layout = ClusterLayout.decode(enc)
            g.system._rebuild_ring()

        for _ in range(400):
            left = len(list(
                leaver.parity_index_table.data.store.items(b"", None)))
            if left == 0:
                break
            await asyncio.sleep(0.05)
        assert left == 0, f"{left} index rows still on removed node"
        # give queued block_ref inserts (the bug's vehicle) time to drain
        await asyncio.sleep(1.0)

        # 1. no parity block-ref was tombstoned anywhere
        survivors = garages[:-1]
        for g in survivors + [leaver]:
            data = g.block_ref_table.data
            for _k, raw in data.store.items(b"", None):
                br = data.decode_entry(raw)
                if is_parity_ref(br.version):
                    assert not br.deleted.value, (
                        "parity shard ref tombstoned by physical offload "
                        f"on {bytes(g.system.id).hex()[:8]}")
        # 2. index rows are still live cluster-wide
        for h in hs:
            assert await live_entries(survivors[0], h), \
                "parity coverage lost after layout offload"
        # 3. every parity shard still exists SOMEWHERE (migration to the
        # new ring placement may still be in flight — what matters is
        # that no shard was GC'd; the buggy decref marked them Deletable)
        seen_ph = set()
        for ent in entries.values():
            for ph in ent.parity_hashes:
                seen_ph.add(bytes(ph))
        for ph in seen_ph:
            assert any(
                g.block_manager.is_block_present(Hash(ph))
                for g in survivors + [leaver]
            ), f"parity shard {ph.hex()[:12]} vanished after offload"
    finally:
        await shutdown(garages)


async def test_parity_gc_sweeper_reclaims_lost_events(tmp_path):
    """The ref-drop GC trigger is one-shot; if it is lost (node down,
    quorum read failed mid-check) the codeword would leak forever.  The
    ParityGcSweeper walks local index rows and reclaims dead codewords
    convergently.  Simulate a lost event by disabling the trigger before
    the deletion, then drive the sweeper directly."""
    import os

    from garage_tpu.model.parity_repair import ParityGcSweeper
    from garage_tpu.utils.background import WorkerState

    garages = await make_ec_cluster(tmp_path, 3)
    try:
        # lose every ref-drop event from here on
        for g in garages:
            g.block_ref_table.data.schema.on_ref_dropped = None

        datas = [os.urandom(15_000 + 11 * i) for i in range(8)]
        hs = [blake2s_sum(d) for d in datas]
        bucket_id = gen_uuid()
        vu = gen_uuid()
        ver = Version.new(vu, bytes(bucket_id), "sweep-obj")
        for off, (h, d) in enumerate(zip(hs, datas)):
            await garages[0].block_manager.rpc_put_block(h, d)
            ver.add_block(0, off, bytes(h), len(d))
        await garages[0].version_table.insert(ver)

        async def live_entries(h):
            ents = await garages[0].parity_index_table.get_range(
                bytes(h), None)
            return [e for e in ents if not e.is_tombstone()]

        for _ in range(400):
            if all([await live_entries(h) for h in hs]):
                break
            await asyncio.sleep(0.05)
        assert all([await live_entries(h) for h in hs])

        # delete the object; with the trigger disabled the index rows
        # must survive (the leak under test)
        await garages[0].version_table.insert(
            Version.new(vu, bytes(bucket_id), "sweep-obj", deleted=True))
        await asyncio.sleep(1.5)
        assert any([await live_entries(h) for h in hs]), \
            "rows tombstoned without the trigger — test setup is wrong"

        # the sweeper reclaims them (age gate dropped for the test)
        for g in garages:
            sw = ParityGcSweeper(g)
            sw.MIN_AGE_MS = 0
            for _ in range(50):
                if await sw.work() == WorkerState.IDLE:
                    break
        for _ in range(100):
            if all([not (await live_entries(h)) for h in hs]):
                break
            await asyncio.sleep(0.05)
        assert all([not (await live_entries(h)) for h in hs]), \
            "sweeper did not reclaim dead codewords"
    finally:
        await shutdown(garages)


async def test_ec_randomized_crash_during_writes(tmp_path):
    """VERDICT r3 #9 (EC stress): continuous S3-style writes into the
    erasure-coded storage class while a random non-writer node crashes
    abruptly mid-stream (possibly mid-put_codeword: parity blocks
    written, index insert racing).  Afterwards the cluster must serve
    every acknowledged object bit-identically — via surviving copies,
    displaced-block peer sweep, or cross-node RS decode."""
    import os
    import random

    from garage_tpu.testing.faults import FaultInjector
    from garage_tpu.utils.data import Hash

    rnd = random.Random(0xEC)
    garages = await make_ec_cluster(tmp_path, 5, rs=(2, 2))
    inj = FaultInjector(garages)
    try:
        bodies = {}
        crash_at = rnd.randrange(6, 18)
        victim = None
        for i in range(24):
            if i == crash_at:
                victim = rnd.randrange(1, 5)
                await inj.crash(victim)
                # drop it from the layout, as an operator would
                from garage_tpu.rpc.layout import ClusterLayout

                lay = ClusterLayout.decode(
                    garages[0].system.layout.encode())
                lay.stage_role(bytes(inj.garages[victim].system.id), None)
                lay.apply_staged_changes()
                enc = lay.encode()
                for j, g in enumerate(garages):
                    if j == victim:
                        continue
                    g.system.layout = ClusterLayout.decode(enc)
                    g.system._rebuild_ring()
            datas = [os.urandom(40_000 + 13 * i + 7 * j)
                     for j in range(3)]
            hs = [blake2s_sum(d) for d in datas]
            vu, bid = gen_uuid(), gen_uuid()
            ver = Version.new(vu, bytes(bid), f"ec-{i}")
            ok = True
            for off, (h, d) in enumerate(zip(hs, datas)):
                try:
                    await garages[0].block_manager.rpc_put_block(h, d)
                    ver.add_block(0, off, bytes(h), len(d))
                except Exception:
                    ok = False  # write raced the crash: not acknowledged
                    break
            if ok:
                try:
                    await garages[0].version_table.insert(ver)
                except Exception:
                    ok = False
            if ok:
                bodies[bytes(vu)] = (ver, datas, hs)
        assert victim is not None and len(bodies) >= 12

        # flush write-time parity, then kick repair on survivors
        for j, g in enumerate(garages):
            if j == victim:
                continue
            if g.block_manager.ec_accumulator is not None:
                await g.block_manager.ec_accumulator.drain()
        for j, g in enumerate(garages):
            if j == victim:
                continue
            for key, _v in g.block_manager.rc.items(b""):
                g.block_manager.resync.put_to_resync(Hash(key[:32]), 0.0)

        async def readable(hs, datas):
            for h, d in zip(hs, datas):
                got = None
                for j, g in enumerate(garages):
                    if j == victim:
                        continue
                    try:
                        got = await g.block_manager.rpc_get_block(
                            Hash(bytes(h)))
                        break
                    except Exception:
                        continue
                if got is None:
                    # direct last line: the sweep + RS decode the resync
                    # path uses
                    g = next(g for j, g in enumerate(garages)
                             if j != victim)
                    got = await g.block_manager.sweep_get_block(
                        Hash(bytes(h)))
                    if got is None and \
                            g.block_manager.parity_reconstructor:
                        got = await g.block_manager.parity_reconstructor(
                            Hash(bytes(h)))
                if got != d:
                    return False
            return True

        import time as _time

        deadline = _time.monotonic() + 120
        missing = dict(bodies)
        while missing and _time.monotonic() < deadline:
            for vu_b in list(missing):
                _ver, datas, hs = missing[vu_b]
                if await readable(hs, datas):
                    del missing[vu_b]
            if missing:
                await asyncio.sleep(1.0)
        assert not missing, \
            f"{len(missing)} acknowledged objects unreadable after crash"
    finally:
        await shutdown([g for j, g in enumerate(inj.garages)
                        if j not in inj.dead])


async def test_scrub_refreshes_lost_distributed_coverage(tmp_path):
    """Coverage is CONVERGENT, not write-time-or-never: a block whose
    distributed codeword was (wrongly) tombstoned — lost GC race, failed
    distribution, pre-EC data — gets re-fed to the write accumulator by
    the next scrub pass and re-covered under a fresh salted gid."""
    import os

    from garage_tpu.block.repair import ScrubWorker

    garages = await make_ec_cluster(tmp_path, 3)
    try:
        datas = [os.urandom(22_000 + 17 * i) for i in range(6)]
        hs = [blake2s_sum(d) for d in datas]
        bucket_id = gen_uuid()
        vu = gen_uuid()
        ver = Version.new(vu, bytes(bucket_id), "cov-obj")
        for off, (h, d) in enumerate(zip(hs, datas)):
            await garages[0].block_manager.rpc_put_block(h, d)
            ver.add_block(0, off, bytes(h), len(d))
        await garages[0].version_table.insert(ver)

        async def live_rows(h):
            ents = await garages[0].parity_index_table.get_range(
                bytes(h), None)
            return [e for e in ents if not e.is_tombstone()]

        for _ in range(400):
            if all([await live_rows(h) for h in hs]):
                break
            await asyncio.sleep(0.05)
        assert all([await live_rows(h) for h in hs])

        # strip coverage: sticky-tombstone EVERY index row (the failure
        # the sweeper could cause before gids were salted)
        for h in hs:
            ents = await garages[0].parity_index_table.get_range(
                bytes(h), None)
            for e in ents:
                e.deleted.set()
            await garages[0].parity_index_table.insert_many(ents)
        for _ in range(100):
            if all([not (await live_rows(h)) for h in hs]):
                break
            await asyncio.sleep(0.05)
        assert all([not (await live_rows(h)) for h in hs])

        # a scrub pass on every node re-covers whatever blocks it stores
        for g in garages:
            g.block_manager.ec_accumulator.flush_after = 0.1
            scrub = ScrubWorker(g.block_manager)
            scrub.send_command("start")
            while (await scrub.work()).name in ("BUSY", "THROTTLED"):
                pass
        for _ in range(400):
            if all([await live_rows(h) for h in hs]):
                break
            await asyncio.sleep(0.05)
        assert all([await live_rows(h) for h in hs]), \
            "scrub did not restore distributed coverage"

        # and the restored coverage actually decodes: a fresh entry for
        # hs[0] must reconstruct the block cross-node
        from garage_tpu.model.parity_repair import make_parity_reconstructor

        rec = await make_parity_reconstructor(garages[0])(
            Hash(bytes(hs[0])))
        assert rec == datas[0]
    finally:
        await shutdown(garages)


async def test_ring_change_sweep_heals_gained_assignment(tmp_path):
    """A node that GAINS the data assignment for a block whose refs it
    ALREADY holds (rc>0 — no 0→1 incref will ever fire, and no table row
    changes on it) must fetch the block automatically after a layout
    change.  With the previous holder CRASHED there is no pusher either:
    the refs-only layout sweep spawned by on_ring_change
    (model/garage.py spawn_workers) is the only trigger.  Before the
    sweep existed this healed only via operator `repair blocks` (the
    bench's degraded phase papered over it with manual resync kicks)."""
    from garage_tpu.rpc.layout import ClusterLayout, NodeRole

    # 4 nodes: meta "3" (ref rows live on 3 of 4 nodes), data "2"
    garages = []
    for i in range(4):
        cfg = config_from_dict({
            "metadata_dir": str(tmp_path / f"n{i}" / "meta"),
            "data_dir": str(tmp_path / f"n{i}" / "data"),
            "replication_mode": "3",
            "data_replication_mode": "2",
            "rpc_bind_addr": "127.0.0.1:0",
            "rpc_secret": "sweep-test",
            "db_engine": "memory",
            "bootstrap_peers": [],
        })
        g = Garage(cfg)
        await g.system.netapp.listen("127.0.0.1:0")
        garages.append(g)
    ports = [g.system.netapp._server.sockets[0].getsockname()[1]
             for g in garages]
    for i, a in enumerate(garages):
        for j, b in enumerate(garages):
            if i < j:
                await a.system.netapp.connect(
                    f"127.0.0.1:{ports[j]}", expected_id=b.system.id)
        a.config.rpc_public_addr = f"127.0.0.1:{ports[i]}"
    lay = garages[0].system.layout
    for g in garages:
        lay.stage_role(bytes(g.system.id), NodeRole("dc1", 1000))
    lay.apply_staged_changes()
    enc = lay.encode()
    for g in garages:
        g.system.layout = ClusterLayout.decode(enc)
        g.system._rebuild_ring()
    for g in garages:
        g.spawn_workers()

    ids = [bytes(g.system.id) for g in garages]
    dead: set = set()

    def by_id(nid):
        return garages[ids.index(bytes(nid))]

    try:
        await _sweep_heal_body(garages, ids, by_id, dead)
    finally:
        for i, g in enumerate(garages):
            if i not in dead:
                try:
                    await g.shutdown()
                except Exception:
                    pass


async def _sweep_heal_body(garages, ids, by_id, dead):
    import os as _os

    from garage_tpu.rpc.layout import ClusterLayout
    from garage_tpu.testing.faults import FaultInjector

    # find a block + victim choice where, after the victim's removal,
    # some node GAINS the data assignment while already holding the refs
    ring0 = garages[0].system.ring
    pick = None
    for seed in range(64):
        data = bytes([seed]) + _os.urandom(4999)
        h = Hash(blake2s_sum(data))
        pre = [bytes(n) for n in ring0.get_nodes(h, 2)]
        meta = [bytes(n) for n in ring0.get_nodes(h, 3)]
        victim_id = pre[0]
        lay2 = ClusterLayout.decode(garages[0].system.layout.encode())
        lay2.stage_role(victim_id, None)
        lay2.apply_staged_changes()
        from garage_tpu.rpc.ring import Ring
        post = [bytes(n) for n in Ring(lay2).get_nodes(h, 2)]
        gained = [n for n in post if n not in pre]
        # beneficiary must have held the refs BEFORE the change
        if gained and gained[0] in meta and gained[0] != victim_id:
            pick = (data, h, victim_id, gained[0], lay2.encode())
            break
    assert pick is not None, "no suitable (block, victim) found in 64 tries"
    data, h, victim_id, gain_id, new_layout = pick

    # seed object/version/refs through node 0 (hook chain populates
    # block_ref + rc on the meta replicas)
    await garages[0].block_manager.rpc_put_block(h, data)
    bucket_id = gen_uuid()
    vu = gen_uuid()
    ver = Version.new(vu, bytes(bucket_id), "obj")
    ver.add_block(0, 0, bytes(h), len(data))
    await garages[0].version_table.insert(ver)
    await garages[0].object_table.insert(
        Object(bucket_id, "obj", [complete_version(vu, 100, b"x")]))

    gainer = by_id(gain_id)
    for _ in range(100):
        rc = gainer.block_manager.rc.get(h)
        if rc is not None and rc.is_needed():
            break
        await asyncio.sleep(0.1)
    rc = gainer.block_manager.rc.get(h)
    assert rc is not None and rc.is_needed(), \
        "precondition: beneficiary must hold refs before the layout change"
    assert not gainer.block_manager.is_block_present(h), \
        "precondition: beneficiary must not hold the block yet"

    # Drain the beneficiary's seed-time resync entry (the 0→1 incref
    # queued a 2 s check; while unassigned it is a dropped no-op) BEFORE
    # the layout change — otherwise that timer, not the sweep, heals the
    # block and this test would pass with the sweep disabled.
    for _ in range(100):
        if gainer.block_resync.queue_len() == 0 and \
                not gainer.block_resync.busy_set:
            break
        await asyncio.sleep(0.25)
    await asyncio.sleep(3.0)
    for _ in range(100):
        if gainer.block_resync.queue_len() == 0 and \
                not gainer.block_resync.busy_set:
            break
        await asyncio.sleep(0.25)
    assert gainer.block_resync.queue_len() == 0
    assert not gainer.block_manager.is_block_present(h), \
        "block appeared before the layout change?!"

    # crash the victim (abrupt — no pusher), then apply the new layout
    inj = FaultInjector(garages)
    await inj.crash(ids.index(victim_id))
    dead.update(inj.dead)
    for i, g in enumerate(garages):
        if i == ids.index(victim_id):
            continue
        g.system.layout = ClusterLayout.decode(new_layout)
        g.system._rebuild_ring()  # fires the refs-only layout sweep

    # the sweep + resync must fetch the block from the surviving holder
    # with NO manual resync kick
    for _ in range(240):
        if gainer.block_manager.is_block_present(h):
            break
        await asyncio.sleep(0.25)
    assert gainer.block_manager.is_block_present(h), \
        "layout sweep did not heal the gained assignment"


async def test_get_survives_silent_sole_copy_loss_via_read_decode(tmp_path):
    """Round-5 regression test for the chaos-soak finding: a block whose
    ONLY copy silently vanishes (disk mishap, no node death, no layout
    change) must still be readable — the GET plane falls back to
    distributed RS decode after every replica fails — and the reader's
    post-decode heal writes the copy back through the put path so it
    re-materializes (block/manager.py streaming fallback +
    _heal_after_decode; resync enqueues are neutralized below so this
    test isolates exactly that write-back).  The reference has no recourse here at all: with the only
    replica gone its GET fails until an operator repair
    (ref src/block/manager.rs:231-317, resync.rs:457-468)."""
    import os

    from garage_tpu.utils.data import blake2s_sum

    garages = await make_ec_cluster(tmp_path, 5)
    try:
        datas = [os.urandom(20_000 + 37 * i) for i in range(12)]
        hs = [blake2s_sum(d) for d in datas]
        for h, d in zip(hs, datas):
            await garages[0].block_manager.rpc_put_block(h, d)
        # wait for write-time parity coverage of some block
        covered = None
        for _ in range(400):
            for h in hs:
                ents = await garages[0].parity_index_table.get_range(
                    bytes(h), None)
                if any(not e.is_tombstone() for e in ents):
                    covered = h
                    break
            if covered is not None:
                break
            await asyncio.sleep(0.05)
        assert covered is not None, "no block gained parity coverage"

        # silently delete the sole copy from its holder's disk.  Resync
        # enqueues are NEUTRALIZED on every node so the assertion below
        # isolates the READ-PATH write-back heal — without it, nothing
        # re-materializes the copy (the resync chain could also heal
        # this config, but then the test would pass with the new code
        # reverted and prove nothing).
        for g in garages:
            g.block_resync.put_to_resync = lambda *a, **k: None
        holder = None
        for g in garages:
            found = g.block_manager.find_block(covered)
            if found is not None:
                holder = g
                os.remove(found[0])
        assert holder is not None, "no node held the block"

        # the GET must succeed NOW via the read-path RS decode
        got = await garages[0].block_manager.rpc_get_block(covered)
        assert got == datas[hs.index(covered)], "decode served wrong bytes"

        # ... and the copy re-materializes via the reader's post-decode
        # write-back (resync is stubbed out — only _heal_after_decode
        # can put the file back; verified by the stub-the-heal negative
        # control in the commit message)
        for _ in range(600):
            if holder.block_manager.is_block_present(covered):
                break
            await asyncio.sleep(0.05)
        assert holder.block_manager.is_block_present(covered), \
            "holder never re-materialized the lost copy"
        blk = await holder.block_manager.read_block(covered)
        assert blk.decompressed() == datas[hs.index(covered)]

        # heal ATTRIBUTION (round-5 heal non-repro): the reader that ran
        # the decode must have recorded exactly a write-back heal — not a
        # resync-chain one (resync was stubbed out above) — and the
        # counter must be scrapeable from its registry
        reader = garages[0].block_manager
        assert reader.heal_counts.get("writeback", 0) >= 1, \
            reader.heal_counts
        assert reader.m_heal.get(source="writeback") >= 1
        assert 'block_heal_total{source="writeback"}' in \
            garages[0].system.metrics.render()
        for g in garages:
            assert g.block_manager.heal_counts.get("resync_fetch", 0) == 0
    finally:
        for g in garages:
            await g.shutdown()

"""Metadata plane at millions of objects — correctness proofs for the
batched paths (ISSUE 14): batched Merkle hashing bit-identical to the
serial per-item updater (including empty/one-leaf/deep-trie edges),
batched sync descent converging identically to the per-node walk on a
diverged pair with ~depth RPC rounds instead of ~nodes, sharded listing
order/continuation-identical to the serial walk under concurrent
inserts, counted-tree / index-counter exactness under delete+reinsert
churn, and a slow-marked 100k-object mini-scale drive."""

import asyncio
import random
import xml.etree.ElementTree as ET

import pytest

from test_s3_api import make_api_cluster, stop_all
from test_table import KVEntry, make_cluster, make_table, shutdown

from garage_tpu.db import open_db
from garage_tpu.db.counted_tree import CountedTree
from garage_tpu.table import TableSyncer
from garage_tpu.table.merkle import EMPTY_HASH, MerkleWorker
from garage_tpu.utils.data import blake2sum
from garage_tpu.utils.promlint import lint_exposition

pytestmark = pytest.mark.asyncio


# --- helpers ---------------------------------------------------------------


def drain_serial(table) -> int:
    """The legacy path: one transaction + root-to-leaf re-hash per item."""
    n = 0
    while True:
        nxt = table.data.merkle_todo.first()
        if nxt is None:
            return n
        table.merkle.update_item(nxt[0])
        n += 1


def drain_batched(table, batch: int = 64) -> int:
    n = 0
    while True:
        items = table.data.merkle_todo.range_scan(limit=batch)
        if not items:
            return n
        n += table.merkle.update_batch(items)


def merkle_dump(table) -> dict:
    return dict(table.data.merkle_tree.items())


def apply_ops(table, ops) -> None:
    for op, entry in ops:
        if op == "put":
            table.data.update_entry(entry.encode())
        else:
            k = entry.tree_key()
            cur = table.data.store.get(k)
            if cur is not None:
                table.data.delete_if_equal(k, cur)


def churn_ops(seed: int, n_keys: int, n_ops: int):
    rng = random.Random(seed)
    ops = []
    for i in range(n_ops):
        key = f"key{rng.randrange(n_keys):05d}"
        if rng.random() < 0.3:
            ops.append(("del", KVEntry("p", key, None, ts=1000 + i)))
        else:
            ops.append(
                ("put", KVEntry("p", key, f"v{i}", ts=1000 + i)))
    return ops


# --- batched Merkle hashing bit-identity -----------------------------------


async def test_merkle_batched_bit_identical_random_churn(tmp_path):
    """Random insert/delete churn drained serially vs batched (several
    batch sizes, including repeated partial drains) produces the exact
    same Merkle tree content and root hashes."""
    systems = await make_cluster(tmp_path, n=1, mode="1")
    for batch in (2, 7, 64, 1024):
        ta = make_table(systems[0], mode="1")
        tb = make_table(systems[0], mode="1")
        ops = churn_ops(seed=batch, n_keys=60, n_ops=200)
        # interleave drains with churn so batches see partial backlogs
        for cut in (50, 120, len(ops)):
            lo = cut - (50 if cut == 50 else (70 if cut == 120 else 80))
            apply_ops(ta, ops[lo:cut])
            apply_ops(tb, ops[lo:cut])
            drain_serial(ta)
            drain_batched(tb, batch=batch)
        assert merkle_dump(ta) == merkle_dump(tb)
        assert ta.data.merkle_todo_len() == 0
        assert tb.data.merkle_todo_len() == 0
        for part in {p for p, _h in ta.replication.partitions()}:
            assert bytes(ta.merkle.partition_root_hash(part)) == bytes(
                tb.merkle.partition_root_hash(part))
    await shutdown(systems)


async def test_merkle_batched_edges(tmp_path):
    """Empty batch, one leaf, delete-to-empty, insert+delete netting to
    nothing, and a deep-trie split (keys whose khash share a 2-byte
    prefix) — all bit-identical to serial."""
    systems = await make_cluster(tmp_path, n=1, mode="1")

    # find two keys whose blake2(tree_key) share the first 2 bytes: the
    # leaf split then recurses two levels (the deep-trie edge)
    ta = make_table(systems[0], mode="1")
    by_prefix = {}
    pair = None
    i = 0
    while pair is None:
        key = f"deep{i}"
        kh = bytes(blake2sum(ta.data.tree_key("p", key)))[:2]
        if kh in by_prefix and by_prefix[kh] != key:
            pair = (by_prefix[kh], key)
        by_prefix.setdefault(kh, key)
        i += 1

    cases = [
        [],  # empty
        [("put", KVEntry("p", "lone", "x", ts=1))],  # one leaf
        [("put", KVEntry("p", "a", "x", ts=1)),
         ("del", KVEntry("p", "a", None, ts=2))],  # net empty
        [("put", KVEntry("p", pair[0], "x", ts=1)),
         ("put", KVEntry("p", pair[1], "y", ts=2))],  # deep split
        [("put", KVEntry("p", pair[0], "x", ts=1)),
         ("put", KVEntry("p", pair[1], "y", ts=2)),
         ("del", KVEntry("p", pair[1], None, ts=3))],  # deep collapse
    ]
    for ops in cases:
        t1 = make_table(systems[0], mode="1")
        t2 = make_table(systems[0], mode="1")
        apply_ops(t1, ops)
        apply_ops(t2, ops)
        drain_serial(t1)
        assert t2.merkle.update_batch([]) == 0
        drain_batched(t2, batch=1024)
        assert merkle_dump(t1) == merkle_dump(t2), ops
    # the net-empty case really is the empty tree
    part = t2.replication.partition_of(blake2sum(b"p"))
    t3 = make_table(systems[0], mode="1")
    apply_ops(t3, cases[2])
    drain_batched(t3)
    assert bytes(t3.merkle.partition_root_hash(part)) == bytes(EMPTY_HASH)
    await shutdown(systems)


async def test_merkle_worker_uses_batched_path(tmp_path):
    """The worker drains through update_batch and re-checks the todo
    queue after a batch (no idle gap on mid-batch refills)."""
    systems = await make_cluster(tmp_path, n=1, mode="1")
    t = make_table(systems[0], mode="1")
    for i in range(30):
        t.data.update_entry(KVEntry("p", f"k{i}", i, ts=10 + i).encode())
    w = MerkleWorker(t.merkle)
    assert w.batch > 1  # default [table] merkle_batch engaged
    state = await w.work()
    assert t.data.merkle_todo_len() == 0
    # a refill right before the status check keeps the worker BUSY
    t.data.update_entry(KVEntry("p", "late", 1, ts=999).encode())
    state = await w.work()
    assert state.name == "BUSY"
    await shutdown(systems)


# --- batched sync descent --------------------------------------------------


async def _make_diverged_pair(tmp_path, n_items: int, seed: int = 7):
    systems = await make_cluster(tmp_path, n=2, mode="2")
    tables = [make_table(s, mode="2") for s in systems]
    syncers = [TableSyncer(s, t.data, t.merkle)
               for s, t in zip(systems, tables)]
    rng = random.Random(seed)
    for i in range(n_items):
        tables[0].data.update_entry(
            KVEntry("p", f"s{i:05d}", rng.random(), ts=100 + i).encode())
    for t in tables:
        drain_batched(t)
    return systems, tables, syncers


async def _sync_all(tables, syncers):
    ph = blake2sum(b"p")
    part = tables[0].replication.partition_of(ph)
    await syncers[0].sync_partition(part, ph)
    for t in tables:
        drain_batched(t)
    return part


async def test_sync_batched_converges_identically(tmp_path):
    """Batched descent pushes the same items as the per-node walk on an
    identically diverged pair, ends at the same root hash, and uses far
    fewer descent RPC rounds (>= 10x at this size)."""
    # pernode baseline
    systems1, tables1, syncers1 = await _make_diverged_pair(tmp_path / "a",
                                                            400)
    for s in syncers1:
        s.sync_batch_nodes = 1
    part = await _sync_all(tables1, syncers1)
    pernode_rpcs = syncers1[0].node_rpcs
    roots1 = {bytes(t.merkle.partition_root_hash(part)) for t in tables1}
    stores1 = [dict(t.data.store.items()) for t in tables1]

    # batched
    systems2, tables2, syncers2 = await _make_diverged_pair(tmp_path / "b",
                                                            400)
    part = await _sync_all(tables2, syncers2)
    batched_rpcs = syncers2[0].node_rpcs
    roots2 = {bytes(t.merkle.partition_root_hash(part)) for t in tables2}
    stores2 = [dict(t.data.store.items()) for t in tables2]

    assert len(roots1) == 1 and len(roots2) == 1
    assert roots1 == roots2
    assert stores1[0] == stores1[1] == stores2[0] == stores2[1]
    assert pernode_rpcs >= 10 * max(batched_rpcs, 1), (
        pernode_rpcs, batched_rpcs)
    await shutdown(systems1)
    await shutdown(systems2)


async def test_sync_batched_falls_back_on_unknown_rpc(tmp_path):
    """A peer without get_nodes (mixed-version) demotes the descent to
    per-node and still converges."""
    systems, tables, syncers = await _make_diverged_pair(tmp_path, 40)

    orig = syncers[1]._handle

    async def no_batch(remote, msg, body):
        if msg.get("t") == "get_nodes":
            from garage_tpu.utils.error import GarageError

            raise GarageError("unknown sync rpc 'get_nodes'")
        return await orig(remote, msg, body)

    syncers[1].endpoint.set_handler(no_batch)
    part = await _sync_all(tables, syncers)
    assert syncers[0]._peer_pernode  # fallback latched
    roots = {bytes(t.merkle.partition_root_hash(part)) for t in tables}
    assert len(roots) == 1
    await shutdown(systems)


# --- sharded listing -------------------------------------------------------


def _parse(body: bytes) -> dict:
    root = ET.fromstring(body)
    for el in root.iter():
        if el.tag.startswith("{"):
            el.tag = el.tag.split("}", 1)[1]
    return {
        "keys": [c.findtext("Key") for c in root.findall("Contents")],
        "prefixes": [p.findtext("Prefix")
                     for p in root.findall("CommonPrefixes")],
        "truncated": root.findtext("IsTruncated"),
        "next_token": root.findtext("NextContinuationToken"),
    }


async def _list_all(client, bucket, shards, garages, **q):
    """Walk a v2 listing to completion under the given shard fan-out,
    returning the concatenated pages (order preserved)."""
    for g in garages:
        g.config.table.list_shards = shards
    out = {"keys": [], "prefixes": [], "pages": 0}
    token = None
    while True:
        query = [("list-type", "2")] + [
            (k.replace("_", "-"), v) for k, v in q.items() if v is not None
        ]
        if token is not None:
            query.append(("continuation-token", token))
        st, _h, body = await client.req("GET", f"/{bucket}", query=query)
        assert st == 200, body[:300]
        page = _parse(body)
        out["keys"] += page["keys"]
        out["prefixes"] += page["prefixes"]
        out["pages"] += 1
        token = page["next_token"]
        if page["truncated"] != "true":
            return out


async def test_sharded_listing_matches_serial(tmp_path):
    """Sharded listing == serial listing: same keys, same order, same
    common prefixes, same continuation behavior — across prefixes,
    delimiters and small max-keys pagination, with concurrent inserts
    landing mid-walk."""
    garages, server, client, _key = await make_api_cluster(tmp_path)
    st, _h, _b = await client.req("PUT", "/shardbkt")
    assert st == 200
    rng = random.Random(3)
    keys = sorted(
        {f"{p}/obj{rng.randrange(10_000):04d}"
         for p in ("alpha", "beta", "zz")
         for _ in range(40)}
        | {f"top{j:03d}" for j in range(25)}
    )
    for k in keys:
        st, _h, _b = await client.req("PUT", f"/shardbkt/{k}", body=b"x")
        assert st == 200, k

    cases = [
        {},
        {"prefix": "alpha/"},
        {"prefix": "beta/", "max_keys": "7"},
        {"delimiter": "/"},
        {"delimiter": "/", "max_keys": "2"},
        {"prefix": "zz/", "delimiter": "/", "max_keys": "5"},
        {"start_after": keys[len(keys) // 2]},
    ]
    for q in cases:
        serial = await _list_all(client, "shardbkt", 1, garages, **q)
        sharded = await _list_all(client, "shardbkt", 6, garages, **q)
        assert serial["keys"] == sharded["keys"], q
        assert serial["prefixes"] == sharded["prefixes"], q

    # concurrent inserts mid-walk: every page stays ordered + dup-free,
    # and every key that existed before the walk appears
    async def insert_more():
        for i in range(30):
            await client.req("PUT", f"/shardbkt/alpha/new{i:03d}", body=b"y")

    task = asyncio.ensure_future(insert_more())
    live = await _list_all(client, "shardbkt", 6, garages, max_keys="20")
    await task
    assert live["keys"] == sorted(live["keys"])
    assert len(live["keys"]) == len(set(live["keys"]))
    assert set(keys) <= set(live["keys"])
    await stop_all(garages, server)


async def test_sharded_listing_fanout_engaged_matches_serial(tmp_path):
    """The shard fan-out only engages when the first page comes back
    FULL (> PAGE keys): a bucket past that threshold, with directories
    both smaller and larger than a page, must list identically serial
    vs sharded — including the delimiter walk whose jumps land BEHIND
    an already-prefetched speculative page (the key-skip regression)."""
    import garage_tpu.api.s3.list as list_mod

    garages, server, client, _key = await make_api_cluster(tmp_path)
    st, _h, _b = await client.req("PUT", "/fanbkt")
    assert st == 200
    # shrink the page so the fan-out threshold is reachable with a
    # test-sized bucket: 60 small dirs (6/dir) + one dir spanning
    # multiple pages
    old_page = list_mod.PAGE
    list_mod.PAGE = 40
    try:
        keys = [f"d{d:02d}/k{i}" for d in range(60) for i in range(6)]
        keys += [f"big/x{i:03d}" for i in range(120)]
        keys.sort()
        for k in keys:
            st, _h, _b = await client.req("PUT", f"/fanbkt/{k}", body=b"x")
            assert st == 200, k
        fanouts0 = garages[0].system.metrics  # fan-out must really engage
        for q in (
            {},
            {"delimiter": "/"},
            {"delimiter": "/", "max_keys": "7"},
            {"prefix": "big/"},
            {"prefix": "d2", "max_keys": "11"},
        ):
            serial = await _list_all(client, "fanbkt", 1, garages, **q)
            sharded = await _list_all(client, "fanbkt", 6, garages, **q)
            assert serial["keys"] == sharded["keys"], q
            assert serial["prefixes"] == sharded["prefixes"], q
        full = await _list_all(client, "fanbkt", 6, garages)
        assert full["keys"] == keys
        assert "api_list_fanout_total" in fanouts0.render()
    finally:
        list_mod.PAGE = old_page
    await stop_all(garages, server)


# --- counted tree / index counter churn ------------------------------------


async def test_counted_tree_exact_under_churn(tmp_path):
    """CountedTree's O(1) count reconciles exactly against the real tree
    length after delete+reinsert churn across every mutation path
    (plain, transactional, compare-and-swap, rollback)."""
    for engine in ("memory", "sqlite"):
        db = open_db(engine, path=(str(tmp_path / f"{engine}.db")
                                   if engine == "sqlite" else None))
        ct = CountedTree(db.open_tree("churn"))
        rng = random.Random(11)
        keys = [f"k{i:03d}".encode() for i in range(50)]
        for step in range(600):
            k = rng.choice(keys)
            mode = rng.randrange(5)
            if mode == 0:
                ct.insert(k, b"v%d" % step)
            elif mode == 1:
                ct.remove(k)
            elif mode == 2:
                def txn(tx, k=k, step=step):
                    if tx.get(ct.tree, k) is None:
                        ct.tx_insert(tx, k, b"t%d" % step)
                    else:
                        ct.tx_remove(tx, k)
                db.transaction(txn)
            elif mode == 3:
                cur = ct.get(k)
                new = None if (cur is not None and rng.random() < 0.5) \
                    else b"c%d" % step
                ct.compare_and_swap(k, cur, new)
            else:
                # aborted transaction: no count skew
                def txn(tx, k=k):
                    ct.tx_insert(tx, k, b"aborted")
                    tx.abort()
                db.transaction(txn)
            assert len(ct) == len(ct.tree), (engine, step, mode)
        assert ct.reconcile() == 0
        db.close()


async def test_index_counter_exact_after_churn(tmp_path):
    """Bucket object counters reconcile exactly with the live rows after
    delete+reinsert churn (the ROADMAP accuracy assertion)."""
    from garage_tpu.utils.data import gen_uuid

    garages, server, client, _key = await make_api_cluster(tmp_path)
    st, _h, _b = await client.req("PUT", "/cntbkt")
    assert st == 200
    rng = random.Random(5)
    keys = [f"obj{i:03d}" for i in range(40)]
    for k in keys:
        await client.req("PUT", f"/cntbkt/{k}", body=b"x" * 64)
    # churn: delete + reinsert a random subset, twice
    for _round in range(2):
        victims = rng.sample(keys, 15)
        for k in victims:
            st, _h, _b = await client.req("DELETE", f"/cntbkt/{k}")
            assert st in (200, 204), st
        for k in victims[:8]:
            await client.req("PUT", f"/cntbkt/{k}", body=b"y" * 32)
        keys = sorted((set(keys) - set(victims)) | set(victims[:8]))
    # drain propagation (insert queues + merkle) on every node
    for _ in range(100):
        if all(len(g.object_counter_table.data.insert_queue) == 0
               and g.object_table.data.merkle_todo_len() == 0
               for g in garages):
            break
        await asyncio.sleep(0.05)
    g = garages[0]
    helper = g.helper()
    bucket_id = await helper.resolve_global_bucket_name("cntbkt")
    totals = await g.object_counter.get_totals(bytes(bucket_id))
    live = await _list_all(client, "cntbkt", 1, garages)
    assert totals.get("objects", 0) == len(live["keys"]) == len(keys), (
        totals, len(live["keys"]), len(keys))
    # counted trees themselves are exact
    for g in garages:
        for t in g.tables:
            assert t.data.merkle_todo.reconcile() == 0
            assert t.data.insert_queue.reconcile() == 0
            assert t.data.gc_todo.reconcile() == 0
    await stop_all(garages, server)


# --- metrics hygiene -------------------------------------------------------


async def test_new_families_promlint(tmp_path):
    """Every new metadata-plane family renders promlint-clean and is
    present after exercising the batched paths."""
    garages, server, client, _key = await make_api_cluster(tmp_path)
    st, _h, _b = await client.req("PUT", "/lintbkt")
    assert st == 200
    for i in range(12):
        await client.req("PUT", f"/lintbkt/k{i:02d}", body=b"x")
    await _list_all(client, "lintbkt", 4, garages)
    for _ in range(100):
        if garages[0].object_table.data.merkle_todo_len() == 0:
            break
        await asyncio.sleep(0.05)
    text = garages[0].system.metrics.render()
    problems = lint_exposition(text)
    assert problems == [], problems
    for fam in ("merkle_batch_items", "merkle_batch_nodes_total",
                "merkle_batch_hash_total", "table_scan_pages_total",
                "table_scan_rows_total", "api_list_pages"):
        assert fam in text, fam
    await stop_all(garages, server)


# --- mini-scale drive ------------------------------------------------------


@pytest.mark.slow
async def test_mini_scale_100k(tmp_path):
    """100k objects through the real table engine: batched Merkle drain,
    sharded deep listing, counters exact — the tier-2 scale proof."""
    from test_model import complete_version

    from garage_tpu.model.s3.object_table import Object
    from garage_tpu.utils.data import gen_uuid

    garages, server, client, _key = await make_api_cluster(tmp_path)
    g = garages[0]
    st, _h, _b = await client.req("PUT", "/scalebkt")
    assert st == 200
    helper = g.helper()
    bucket_id = await helper.resolve_global_bucket_name("scalebkt")
    n = 100_000

    def load():
        data = g.object_table.data
        for i in range(n):
            v = complete_version(gen_uuid(), 1000 + i, b"")
            data.update_entry(
                Object(bucket_id, f"obj{i:06d}", [v]).encode())

    await asyncio.to_thread(load)
    assert g.object_table.data.store_len() >= n
    # batched drain of the whole backlog
    await asyncio.to_thread(drain_batched, g.object_table, 512)
    assert g.object_table.data.merkle_todo_len() == 0
    # deep sharded listing over a 10k-key prefix agrees with the key set
    # (listing ALL 100k via quorum XML pages is minutes of pure decode)
    listed = await _list_all(client, "scalebkt", 8, garages,
                             prefix="obj01", max_keys="1000")
    assert len(listed["keys"]) == sum(
        1 for i in range(n) if f"obj{i:06d}".startswith("obj01"))
    assert listed["keys"] == sorted(listed["keys"])
    # counters exact at scale (propagation drained)
    for _ in range(600):
        if all(len(t.data.insert_queue) == 0 for t in g.tables):
            break
        await asyncio.sleep(0.1)
    totals = await g.object_counter.get_totals(bytes(bucket_id))
    assert totals.get("objects", 0) == n
    await stop_all(garages, server)

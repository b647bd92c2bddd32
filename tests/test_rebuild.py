"""Tree-aggregated PPR, chain repair, and the fleet rebuild scheduler.

Unit half: a stub aggregation tree over hand-built codewords proves the
tree output is bit-identical to flat PPR and serial decode (all survivor
patterns x m' in {1, 2}), that a mid-tree node death re-plans the lost
subtree (never aborting the codeword), that a mixed-version peer demotes
its edge to flat PPR, and that chain repair decodes every lost row from
ONE k-piece fetch set.

Scheduler half: RebuildCheckpoint/RebuildScheduler over fake stores —
the walk heals every lost block exactly once, owns() dedupes against
resync, failures park back onto the queue with source="rebuild", and a
coordinator restart RESUMES from the checkpoint instead of restarting.
"""

import asyncio
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_repair_plan import (  # noqa: E402
    FakeManager,
    FakeRpc,
    StubPlanner,
    make_codeword,
)

from garage_tpu.block.rebuild import (  # noqa: E402
    RebuildCheckpoint,
    RebuildScheduler,
)
from garage_tpu.ops import gf256  # noqa: E402
from garage_tpu.utils.data import Hash  # noqa: E402
from garage_tpu.utils.error import GarageError  # noqa: E402
from garage_tpu.utils.persister import Persister  # noqa: E402

pytestmark = pytest.mark.asyncio


# --- tree fakes --------------------------------------------------------------


class TreeRpc(FakeRpc):
    def peer_allows(self, n):
        return True

    def note_result(self, n, e):
        pass

    def timeout_for(self, n, t):
        return t


class TreeStubPlanner(StubPlanner):
    """Planner whose `_call_tree` simulates the whole aggregation tree
    locally from the shard dictionary: per-node death marks that node's
    subtree missing (exactly what a dead interior node produces on the
    wire), a dead ROOT raises (exactly what the coordinator sees)."""

    def __init__(self, mgr, shards, node_of_piece, **kw):
        super().__init__(mgr, shards, **kw)
        self.node_of_piece = node_of_piece  # piece hash -> node id
        self.dead_nodes = set()
        self.tree_calls = []

    async def _call_tree(self, node, msg, depth):
        if bytes(node) in self.dead_nodes:
            raise GarageError("injected root death")
        self.tree_calls.append((bytes(node), msg["plan"], depth))
        wants = msg["want"]
        accs = [np.zeros(w, dtype=np.uint8) for w in wants]
        got, miss = [], []

        def indexes(plan):
            out = [int(p[3]) for p in plan["p"]]
            for _n, sub in plan["c"]:
                out.extend(indexes(sub))
            return out

        def serve(plan, nid):
            if nid in self.dead_nodes:
                miss.extend(indexes(plan))
                return
            for hb, _par, coeffs, idx in plan["p"]:
                sh = self.shards[bytes(hb)]
                for j, (c, w) in enumerate(zip(coeffs, wants)):
                    if not c:
                        continue
                    data = gf256.gf_scale_bytes(int(c), sh, w)
                    arr = np.frombuffer(data, dtype=np.uint8)
                    accs[j][: len(arr)] ^= arr
                got.append(int(idx))
            for cnode, sub in plan["c"]:
                serve(sub, bytes(cnode))

        serve(msg["plan"], bytes(node))
        return got, miss, b"".join(a.tobytes() for a in accs)


def make_tree_setup(k=4, m=2, sizes=(1000, 900, 800, 700), seed=11,
                    versions=None):
    """A codeword whose 6 pieces live on 6 DISTINCT ranked nodes, and a
    tree planner over them."""
    ent, shards, datas = make_codeword(k=k, m=m, sizes=sizes, seed=seed)
    piece_hashes = list(ent.members) + list(ent.parity_hashes)
    nodes = [bytes([0x10 + i]) * 32 for i in range(len(piece_hashes))]
    holders = {h: [n] for h, n in zip(piece_hashes, nodes)}
    # strictly increasing rank → deterministic tree shape (member order)
    ranks = {n: (1, 0, 0.001 * (i + 1)) for i, n in enumerate(nodes)}
    mgr = FakeManager(holders=holders, ranks=ranks)
    mgr.system.rpc = TreeRpc(ranks)
    if versions:
        vmap = dict(versions)
        mgr.system.peer_version = lambda nid: vmap.get(bytes(nid))
    node_of = {h: n for h, n in zip(piece_hashes, nodes)}
    pl = TreeStubPlanner(mgr, shards, node_of, use_ppr=True, use_tree=True)
    return ent, shards, datas, mgr, pl, node_of


# --- tree-aggregated PPR -----------------------------------------------------


async def test_tree_output_bit_identical_to_flat_ppr():
    ent, shards, datas, mgr, pl, _ = make_tree_setup()
    out = await pl.reconstruct(Hash(ent.members[0]), ent)
    assert out == datas[0]
    assert pl.tree_plans == 1
    assert pl.fetch_log == [], "tree path must not fetch flat"
    # coordinator ingress: ONE aggregated stream, flat in k — exactly
    # the target row's length, counted under mode "tree"
    assert mgr.counters["fetch"].get("tree") == ent.lengths[0]
    assert mgr.counters["fetch"].get("ppr", 0) == 0
    # flat reference on a fresh manager: same bytes
    mgr2 = FakeManager()
    flat = await StubPlanner(mgr2, shards, use_ppr=True).reconstruct(
        Hash(ent.members[0]), ent)
    assert flat == out == datas[0]


async def test_tree_all_single_survivor_losses_stay_bit_identical():
    """Every pattern of one additional dead NON-ROOT piece-holder (a
    tree child): the subtree re-plan completes flat, bit-identically.
    (A dead ROOT aborts to the flat planner — separate test below.)"""
    for dead_i in range(2, 4):  # survivors are members 1..3 + P0; 1 = root
        ent, shards, datas, mgr, pl, node_of = make_tree_setup()
        pl.dead_nodes = {node_of[ent.members[dead_i]]}
        out = await pl.reconstruct(Hash(ent.members[0]), ent)
        assert out == datas[0], f"dead piece {dead_i}"
        assert pl.replans.get("mid_tree", 0) >= 1
        # the missing piece was re-fetched flat with the NEUTRAL
        # coefficient (same survivor set — aggregate stays valid)
        assert ("ppr", dead_i, 1) in pl.fetch_log


async def test_tree_root_death_aborts_to_flat_planner():
    ent, shards, datas, mgr, pl, node_of = make_tree_setup()
    pl.dead_nodes = {node_of[ent.members[1]]}  # rank-first → tree root
    # kill the root's shard for the flat path too?  No: flat re-plan
    # must succeed from the SAME pieces via per-piece fetches
    out = await pl.reconstruct(Hash(ent.members[0]), ent)
    assert out == datas[0]
    assert pl.replans.get("tree_abort", 0) >= 1
    assert len(pl.fetch_log) >= ent.k, "flat planner took over"


async def test_mixed_version_edge_demotes_to_flat_ppr():
    ent, shards, datas, mgr, pl, node_of = make_tree_setup()
    old = node_of[ent.members[2]]
    vmap = {old: "0.9.0"}  # PPR-capable, pre-tree
    mgr.system.peer_version = lambda nid: vmap.get(bytes(nid))
    out = await pl.reconstruct(Hash(ent.members[0]), ent)
    assert out == datas[0]
    assert pl.tree_plans == 1, "tree still used for capable peers"
    assert pl.replans.get("version_demote", 0) == 1
    assert pl.tree_demotions == 1
    # the demoted edge's piece moved flat, the rest as one tree stream
    assert ("ppr", 2, 1) in pl.fetch_log
    assert mgr.counters["fetch"].get("tree") == ent.lengths[0]


async def test_tree_chain_decodes_two_targets_from_one_stream():
    ent, shards, datas, mgr, pl, _ = make_tree_setup()
    out = await pl.reconstruct_group(ent, [0, 1])
    assert out[0] == datas[0] and out[1] == datas[1]
    assert pl.tree_plans == 1
    # ONE aggregated stream carrying BOTH rows: ingress = sum of the
    # two target lengths, still flat in k
    assert mgr.counters["fetch"].get("tree") == (
        ent.lengths[0] + ent.lengths[1])


# --- chain repair, flat transport --------------------------------------------


@pytest.mark.parametrize("use_ppr", [True, False])
async def test_chain_repair_two_lost_rows_share_one_fetch_set(use_ppr):
    ent, shards, datas = make_codeword(k=2, m=2, sizes=(640, 480))
    mgr = FakeManager()
    pl = StubPlanner(mgr, shards, use_ppr=use_ppr, use_tree=False)
    out = await pl.reconstruct_group(ent, [0, 1])
    assert out[0] == datas[0] and out[1] == datas[1]
    # m' = 2 lost rows, exactly k = 2 fetches TOTAL — not k per target
    assert len(pl.fetch_log) == ent.k, pl.fetch_log
    assert mgr.counters["repaired"] == len(datas[0]) + len(datas[1])


@pytest.mark.parametrize("m_prime", [1, 2])
async def test_chain_outputs_match_serial_decode(m_prime):
    """All survivor patterns x m' in {1,2}: chain output == per-target
    serial decode, for every choice of which piece-fetch fails."""
    targets = list(range(m_prime))
    ent, shards, datas = make_codeword(k=3, m=2,
                                       sizes=(900, 700, 500), seed=23)
    cands = [i for i in range(5) if i not in targets]
    piece_hash = {i: (ent.members[i] if i < 3
                      else ent.parity_hashes[i - 3]) for i in range(5)}
    spare = len(cands) - ent.k  # how many failures stay recoverable
    fail_choices = [None] + [cands[i] for i in range(len(cands))][:spare + 2]
    for fail in fail_choices:
        mgr = FakeManager()
        pl = StubPlanner(mgr, shards, use_ppr=True, use_tree=False,
                         hedge_delay=5.0)
        if fail is not None:
            pl.behavior[piece_hash[fail]] = "fail"
        group = await pl.reconstruct_group(ent, targets)
        recoverable = fail is None or spare >= 1
        for t in targets:
            serial_mgr = FakeManager()
            serial = StubPlanner(serial_mgr, shards, use_ppr=True,
                                 use_tree=False, hedge_delay=5.0)
            if fail is not None:
                serial.behavior[piece_hash[fail]] = "fail"
            for u in targets:  # every lost row is gone for serial too
                if u != t:
                    serial.behavior[ent.members[u]] = "fail"
            want = await serial.reconstruct(Hash(ent.members[t]), ent)
            if recoverable:
                assert group[t] == want == datas[t], (fail, t)
            else:
                assert group.get(t) is None and want is None
        if fail is not None and recoverable:
            assert pl.replans.get("survivor_died", 0) >= 1


async def test_survivor_death_mid_ppr_counts_replan():
    """Satellite: a survivor dying after acking the plan re-plans with
    the next-ranked replacement — counted, never a codeword abort."""
    ent, shards, datas = make_codeword()
    mgr = FakeManager()
    pl = StubPlanner(mgr, shards, use_ppr=True, use_tree=False,
                     hedge_delay=5.0)
    pl.behavior[ent.members[2]] = "fail"
    out = await pl.reconstruct(Hash(ent.members[0]), ent)
    assert out == datas[0]
    assert pl.replans.get("survivor_died", 0) == 1


# --- scheduler fakes ---------------------------------------------------------


class FakeRcEntry:
    def is_needed(self):
        return True


class FakeRcTree:
    def __init__(self, keys):
        self.keys = sorted(keys)

    def first(self):
        return (self.keys[0], b"") if self.keys else None


class FakeRc:
    def __init__(self, keys):
        self.tree = FakeRcTree(keys)

    def get(self, h):
        return FakeRcEntry()

    def get_gt(self, key):
        for k in self.tree.keys:
            if k > bytes(key):
                return (k, b"")
        return None


class FakeBlockStore:
    """manager-shaped fake for the scheduler: rc walk, presence set,
    write_block, heal counters."""

    def __init__(self, keys):
        self.rc = FakeRc(keys)
        self.present = set()
        self.writes = []
        self.heals = []
        self.blocks_reconstructed = 0

        class _Repl:
            def read_nodes(self, h):
                return [b"\x01" * 32]

            def write_nodes(self, h):
                return [b"\x01" * 32]

        class _Sys:
            id = b"\x00" * 32

        self.replication = _Repl()
        self.system = _Sys()

    def is_block_present(self, h):
        return bytes(h) in self.present

    def is_assigned(self, h):
        return True

    async def store_rebuilt(self, h, content):
        self.writes.append(bytes(h))
        self.present.add(bytes(h))

    def note_heal(self, source):
        self.heals.append(source)


class FakeResync:
    def __init__(self):
        self.busy_set = set()
        self.parked = []
        self.rebuild = None
        self.rebuild_skips = 0

    def put_to_resync(self, h, delay, source="other"):
        self.parked.append((bytes(h), source))


def sched_fixture(tmp_path, n_blocks=20, partition=0x42, uncovered=()):
    datas = {}
    keys = []
    for i in range(n_blocks):
        hb = bytes([partition]) + bytes([i]) + os.urandom(30)
        keys.append(hb)
        datas[hb] = os.urandom(100 + i)
    mgr = FakeBlockStore(keys)
    resync = FakeResync()

    class _Ent:
        def __init__(self, hb):
            self.k, self.m = 1, 1
            self.member_index = 0
            self.members = [hb]
            self.lengths = [len(datas[hb])]
            self.parity_hashes = []

    async def lookup(h):
        if bytes(h) in uncovered:
            return []
        return [_Ent(bytes(h))]

    async def decode(h, ent):
        return datas[bytes(h)]

    def make(rate=1e9):
        s = RebuildScheduler(
            mgr, resync, rate_mib_s=rate,
            persister=Persister(str(tmp_path), "rebuild_sched",
                                RebuildCheckpoint),
            governor=None, lookup=lookup, decode_fallback=decode)
        resync.rebuild = s
        return s

    return mgr, resync, keys, datas, make


# --- scheduler ---------------------------------------------------------------


async def test_scheduler_heals_every_lost_block_exactly_once(tmp_path):
    mgr, resync, keys, datas, make = sched_fixture(tmp_path)
    s = make()
    s.node_lost([0x42], b"ring-a")
    while s._pending:
        await s.work()
    assert sorted(mgr.writes) == sorted(keys)
    assert len(mgr.writes) == len(set(mgr.writes)), "a block healed twice"
    assert mgr.heals == ["rebuild"] * len(keys)
    assert s.partitions_done == s.partitions_total == 1
    assert s.blocks_healed == len(keys)
    assert s.bytes_healed == sum(len(d) for d in datas.values())
    assert s.paced_sleeps > 0
    assert not s.owns(keys[0]), "completed run must release ownership"


async def test_scheduler_owns_dedupes_resync(tmp_path):
    mgr, resync, keys, datas, make = sched_fixture(tmp_path)
    s = make()
    s.node_lost([0x42], b"ring-a")
    ordered = sorted(keys)
    assert s.owns(ordered[0]) and s.owns(ordered[-1])
    assert not s.owns(b"\x43" + ordered[0][1:]), "other partition"
    await s.work()  # one batch: REBUILD_BATCH blocks walked
    assert not s.owns(ordered[0]), "walked hashes are released"
    assert s.owns(ordered[-1]), "un-walked hashes stay claimed"
    # a present block is skipped without rebuilding
    assert ordered[0] in mgr.writes

    # the real resync seam: owns() → drop, count, never double-repair
    from garage_tpu.block.resync import BlockResyncManager
    from garage_tpu.db import open_db

    class _M:
        class system:
            metrics = None

    rsm = BlockResyncManager(_M(), open_db("memory"))
    rsm.rebuild = s
    rsm.put_to_resync(Hash(ordered[-1]), 0.0, source="layout_sweep")
    assert rsm.queue_len() == 1
    await rsm.resync_iter()
    assert rsm.queue_len() == 0 and rsm.rebuild_skips == 1
    moved = await rsm.rebalance_hash(Hash(ordered[-1]))
    assert moved == 0 and rsm.rebuild_skips == 2


async def test_scheduler_checkpoint_resume_after_restart(tmp_path):
    mgr, resync, keys, datas, make = sched_fixture(tmp_path)
    s1 = make()
    s1.node_lost([0x42, 0x99], b"ring-a")  # 0x99 is empty: walks clean
    await s1.work()  # one batch, then the coordinator "crashes"
    done_before = list(mgr.writes)
    assert 0 < len(done_before) < len(keys)

    s2 = make()
    assert not s2.maybe_resume(b"ring-B"), "stale ring must not resume"
    s3 = make()
    # the stale-ring discard persisted an inactive checkpoint — write a
    # fresh one as the crash left it
    s1._checkpoint(force=True)
    assert s3.maybe_resume(b"ring-a")
    assert s3.partitions_total == 2
    while s3._pending:
        await s3.work()
    assert sorted(mgr.writes) == sorted(keys)
    assert len(mgr.writes) == len(set(mgr.writes)), \
        "resume must not re-heal blocks the first run finished"
    assert s3.partitions_done == 2
    # completed: a fresh scheduler finds nothing to resume
    s4 = make()
    assert not s4.maybe_resume(b"ring-a")


async def test_scheduler_parks_failures_with_rebuild_source(tmp_path):
    mgr, resync, keys, datas, make = sched_fixture(tmp_path)
    uncovered = set(sorted(keys)[:2])

    async def lookup_none(h):
        if bytes(h) in uncovered:
            return []
        class _Ent:
            k = m = 1
            member_index = 0
            parity_hashes = []
            def __init__(s2, hb):
                s2.members = [hb]
                s2.lengths = [len(datas[hb])]
        return [_Ent(bytes(h))]

    async def decode(h, ent):
        return datas[bytes(h)]

    s = RebuildScheduler(
        mgr, resync, rate_mib_s=1e9,
        persister=Persister(str(tmp_path), "rebuild_sched2",
                            RebuildCheckpoint),
        lookup=lookup_none, decode_fallback=decode)
    resync.rebuild = s
    s.node_lost([0x42], b"ring-a")
    while s._pending:
        await s.work()
    assert sorted(hb for hb, _ in resync.parked) == sorted(uncovered)
    assert all(src == "rebuild" for _, src in resync.parked)
    # parked hashes were NOT healed; everything else was
    assert sorted(mgr.writes) == sorted(set(keys) - uncovered)
    for hb, _ in resync.parked:
        assert not s.owns(hb), "parked hashes must be released to resync"


async def test_late_ref_rearms_completed_walk(tmp_path):
    """Table sync lags the ring change: a ref that lands AFTER the walk
    finished its partition must re-queue it (note_ref), so the late
    block heals through the scheduler, not a one-off resync."""
    mgr, resync, keys, datas, make = sched_fixture(tmp_path)
    s = make()
    s.node_lost([0x42], b"ring-a")
    while s._pending:
        await s.work()
    assert s.idle() and s.blocks_healed == len(keys)

    late = bytes([0x42]) + b"\xfe" + os.urandom(30)
    datas[late] = os.urandom(321)
    mgr.rc.tree.keys = sorted(mgr.rc.tree.keys + [late])
    assert s.note_ref(Hash(late)), "in-window late ref must re-arm"
    assert not s.idle() and s.rearms == 1
    while s._pending:
        await s.work()
    assert late in mgr.writes
    assert s.partitions_done == s.partitions_total == 2
    # outside the loss's partitions: not ours, untouched
    other = b"\x43" + os.urandom(31)
    assert not s.note_ref(Hash(other))
    # window expiry: the re-arm horizon is bounded
    s._rearm_until = 0.0
    assert not s.note_ref(Hash(late))
    assert s.idle()


async def test_late_ref_behind_cursor_rewalks_partition(tmp_path):
    """A ref landing BEHIND the live cursor mid-walk re-walks the
    partition after the current pass instead of being skipped."""
    mgr, resync, keys, datas, make = sched_fixture(tmp_path)
    s = make()
    s.node_lost([0x42], b"ring-a")
    await s.work()  # one batch: cursor now inside the partition
    assert s._cursor is not None
    late = bytes([0x42]) + b"\x00" * 31  # sorts before every walked key
    datas[late] = os.urandom(77)
    mgr.rc.tree.keys = sorted(mgr.rc.tree.keys + [late])
    assert bytes(late) <= s._cursor, "test premise: key is behind cursor"
    assert s.note_ref(Hash(late))
    while s._pending:
        await s.work()
    assert late in mgr.writes, "rewalk pass must heal the late block"
    assert len(mgr.writes) == len(set(mgr.writes)), "no double heals"
    assert s.rearms == 1 and s.idle()

"""The `ec84-ingest` deployment: its model of the bucket, the checks of
its kind over codewords of a grouping the program does not make, and
its cell end to end at a tiny size on the CPU: a traced run comes out
correct and reads what the deployment added; the control fails by the
parity; a scrub that skips the blocks written since set-up, and a
program that acknowledges a PUT held by one node, are each seen by the
count that names them.  (`test_cells.py` runs the cell's untraced run
and its control with the mix's own 1 MiB objects, 16 blocks each at the
tiny size, as every cell's.)"""

import asyncio
import os
import types

import msgpack
import numpy as np
import pytest

from benchmarks import cluster as cl
from benchmarks import harness
from benchmarks import reference as plain
from benchmarks.tests.tiny import run, tiny

CELL = "ec84-ingest.scrub"
NEW = ("scrub_purge_ms_per_gib", "scrub_sidecar_rewrite_share.scrub",
       "parity_purged_per_pass.scrub", "tpu_byte_share.hash",
       "ingest_put_ms.scrub")
COUNTS = {"blocks_wrong", "plants_missed", "puts_unreplicated", "unverified",
          "parity_wrong", "unprotected", "gets_wrong"}


def tiny_ingest(cell):
    """`tiny`, and an ingested object is one block, as at full size."""
    tiny(cell)
    size = cell.config["block_size"]
    cell.mix["object_bytes"] = cell.config["ingest"]["object_bytes"] = size
    return cell


def failing(res) -> set:
    return {name for name, c in res["compared"].items()
            if c["value"] > c["limit"]}


def test_the_configuration_is_ec84_1m_and_what_the_bucket_takes_between_passes():
    cell, static = harness.Cell(CELL), harness.Cell("ec84-1m.scrub")
    own = {"name", "source", "ingest", "guarantees", "assumed"}
    assert set(cell.config) - set(static.config) == {"ingest"}
    for key in set(static.config) - own:
        assert cell.config[key] == static.config[key], key
    assert cell.config["guarantees"][:3] == static.config["guarantees"]
    assert len(cell.config["guarantees"]) == 5
    assert static.config["assumed"].items() <= cell.config["assumed"].items()
    assert cell.config["ingest"] == {
        "objects_per_pass": cell.mix["objects_per_pass"],
        "object_bytes": cell.mix["object_bytes"]}
    assert cell.mix["objects_per_pass"] in (2, 4)
    assert cell.mix["object_bytes"] == cell.config["block_size"] == 1 << 20
    assert (cell.mix["corrupt_per_pass"], cell.mix["sidecars_per_pass"],
            cell.mix["node_under_test"]) == (2, 0, 1)
    listed = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= listed
    assert listed - set(NEW) == {
        m["name"] for m in static.per_layer()} - {
            "xla_scan_compute_share.scrub"} - set(NEW)


def test_the_bucket_model_gives_a_seeds_ids_and_bytes():
    ref = harness.Cell(CELL).reference
    seed, block = 2**31 + 23, 4096
    plan = [("small/0000", 0, block), ("big/00", 1, 3 * block)]
    bucket = ref.Bucket(seed, block, plan)
    assert bucket.blocks() == 4 and len(bucket.block_ids()) == 4
    # an object's bytes are every other configuration's for its (seed,
    # index), and its blocks' ids are BLAKE2s of its cuts
    body = cl.object_bytes(seed, 1, 3 * block)
    assert bucket.reads_as("big/00") == body
    assert bucket.ids_of("big/00") == [
        plain.block_id(body[o:o + block]) for o in range(0, len(body), block)]
    before = bucket.block_ids()
    bucket.acknowledged("ingest/1/0", 7, block + 1)     # two blocks
    assert bucket.blocks() == 6
    assert bucket.block_ids() - before == set(bucket.ids_of("ingest/1/0"))
    assert bucket.reads_as("ingest/1/0") == cl.object_bytes(
        seed, 7, block + 1)
    other = ref.Bucket(seed + 1, block, plan)
    assert not other.block_ids() & before
    assert ref.Bucket(seed, block, plan).block_ids() == before


def stored_codewords(tmp_path, groups, parity_fn=plain.rs_parity, k=8, m=4):
    """A data dir with the blocks of `groups` and one sidecar a group,
    as the reference encodes it: → (state for the kind's checks, the
    blocks by id)."""
    data = tmp_path / "data"
    blocks = {}
    for gi, group in enumerate(groups):
        for raw in group:
            h = plain.block_id(raw)
            blocks[h] = raw
            path = data / h[:2] / h[2:4] / h
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_bytes(raw)
        maxlen = max(map(len, group))
        parity = plain.codeword_parity(group, maxlen, k, m, parity_fn)
        side = data / "parity" / f"{gi:02x}" / f"{gi:064x}.par"
        side.parent.mkdir(parents=True, exist_ok=True)
        side.write_bytes(msgpack.packb({
            "v": 1, "k": k, "m": m, "maxlen": maxlen,
            "hashes": [bytes.fromhex(plain.block_id(raw)) for raw in group],
            "lengths": [len(raw) for raw in group],
            "parity": [parity[i].tobytes() for i in range(m)]},
            use_bin_type=True))
    st = types.SimpleNamespace(
        data_dir=str(data), reference=harness.Cell(CELL).reference,
        config={"rs_data": k, "rs_parity": m})
    return st, blocks


def test_the_checks_judge_codewords_of_another_grouping(tmp_path):
    """Codewords of 5, 8, 1 and 3 members in no order of ids, one block
    in two of them: the program makes none of these, the reference
    encodes them and the kind's checks judge each as what it states."""
    kind = harness.Cell(CELL).kind
    rng = np.random.default_rng(5)
    raws = [rng.bytes(int(rng.integers(500, 900))) for _ in range(16)]
    groups = [raws[0:5], raws[5:13][::-1], raws[13:14],
              [raws[15], raws[2], raws[14]]]
    st, blocks = stored_codewords(tmp_path, groups)
    on_disk = kind.sidecars_on_disk(st)
    assert sorted(len(members) for _p, members, _t in on_disk) == [1, 3, 5, 8]
    assert kind.check_sidecars(st, on_disk, seed=9) == (0, 4)
    assert {h for _p, ms, _t in on_disk for h in ms} == set(blocks)
    # the same codewords with parity in GF(2), the control's: all wrong
    st2, _ = stored_codewords(tmp_path / "gf2", groups,
                              parity_fn=plain.rs_parity_xor_only)
    assert kind.check_sidecars(st2, kind.sidecars_on_disk(st2),
                               seed=9) == (4, 4)
    # a member the store lacks
    gone = plain.block_id(raws[13])
    os.remove(tmp_path / "data" / gone[:2] / gone[2:4] / gone)
    assert kind.check_sidecars(st, kind.sidecars_on_disk(st),
                               seed=9) == (1, 4)


def test_the_sample_read_back_is_the_static_cells_and_six_of_the_ingested():
    cell = harness.Cell(CELL)
    kind = cell.kind
    plan = kind.base.object_plan(cell.config["store"],
                                 cell.config["block_size"])
    st = types.SimpleNamespace(plan=plan, ingested=[
        (f"ingest/{p}/{i}", 1) for p in range(1, 9) for i in range(4)])
    keys = kind.get_sample(st, seed=2**31 + 3)
    old, new = keys[:13], keys[13:]
    assert len(set(old)) == 13 and old[-1] == plan[-1][0]
    assert set(old) <= {key for key, _i, _n in plan}
    assert new[0] == "ingest/1/0" and new[-1] == "ingest/8/3"
    assert len(set(new)) == 6 and set(new) <= {k for k, _n in st.ingested}
    assert keys == kind.get_sample(st, seed=2**31 + 3)
    st.ingested = st.ingested[:1]
    assert kind.get_sample(st, seed=1)[13:] == ["ingest/1/0"]


def test_a_mix_that_is_not_the_configurations_is_refused():
    cell = tiny_ingest(harness.Cell(CELL))
    cell.mix["objects_per_pass"] = 3
    with pytest.raises(RuntimeError, match="is not the configuration's"):
        run(cell, seed=3)


def test_a_traced_run_is_correct_and_reads_what_the_deployment_added():
    cell = tiny_ingest(harness.Cell(CELL))
    res = run(cell, seed=2**31 + 29, trace=True)
    assert res["correct"], res["compared"]
    assert set(res["compared"]) == COUNTS
    assert res["failed"] == 0 and res["attempted"] > 0
    got = {name: m["value"] for name, m in res["metrics"].items()}
    assert set(NEW) <= set(got)
    # codewords moved: most were written anew and as many purged
    static = 100.0 * 4 / 6      # the four sidecars `scrub.json` removes
    assert got["parity_fetch_share.scrub"] > static
    assert 0 < got["scrub_sidecar_rewrite_share.scrub"] <= 50.0
    assert got["parity_purged_per_pass.scrub"] > 0
    assert got["scrub_purge_ms_per_gib"] > 0
    assert got["ingest_put_ms.scrub"] > 0
    assert 0 <= got["tpu_byte_share.hash"] <= 100
    assert got["compiles_per_pass.scrub"] == 0


def test_the_control_fails_by_the_parity():
    cell = tiny_ingest(harness.Cell(CELL))
    res = run(cell, seed=37, seconds=1.0,
              after_cluster=cell.kind.control(cell))
    assert not res["correct"]
    assert cell.reference.CONTROL_FAILS_BY == "parity_wrong" in failing(res)
    assert not failing(res) & {"puts_unreplicated", "unverified",
                               "gets_wrong"}


def test_a_scrub_that_skips_blocks_newer_than_set_up_fails_by_unverified():
    cell = tiny_ingest(harness.Cell(CELL))

    def install(st):
        worker = st.cluster.garages[st.node].scrub_worker
        real = worker.scrub_batch

        async def only_what_set_up_listed(batch, reads=None):
            keep = [i for i, (h, _p, _c) in enumerate(batch)
                    if bytes(h).hex() in st.size_of]
            return await real(
                [batch[i] for i in keep],
                None if reads is None else [reads[i] for i in keep])

        worker.scrub_batch = only_what_set_up_listed

    res = run(cell, seed=41, seconds=1.0, after_cluster=install)
    assert not res["correct"]
    assert "unverified" in failing(res)
    # every pass of the window missed the objects of each round before
    # it, set-up's two warm rounds or more and its own
    assert res["compared"]["unverified"]["value"] >= 3 * cell.mix[
        "objects_per_pass"]
    assert not failing(res) & {"puts_unreplicated", "gets_wrong",
                               "plants_missed"}


def test_a_program_that_acknowledges_on_one_node_fails_by_puts_unreplicated():
    cell = tiny_ingest(harness.Cell(CELL))
    late = set()

    def install(st):
        mgr = st.cluster.garages[0].block_manager
        real = mgr.rpc_put_block

        async def acknowledge_early(h, data, **kw):
            await mgr.write_block(h, await mgr.block_for_storage(data))

            async def the_others():
                await asyncio.sleep(0.05)
                await real(h, data, **kw)

            task = asyncio.ensure_future(the_others())
            late.add(task)
            task.add_done_callback(late.discard)

        mgr.rpc_put_block = acknowledge_early

    res = run(cell, seed=43, seconds=1.0, after_cluster=install)
    assert not res["correct"]
    assert "puts_unreplicated" in failing(res)
    assert not failing(res) & {"blocks_wrong", "unverified", "gets_wrong",
                               "parity_wrong"}

"""The `ec84-warp` deployment: its sized plan, and its cell end to end at
a tiny size on the CPU, as `test_cells.py` runs the others: the program
comes out correct, its control does not, a timed path broken underneath
is seen, and a traced run reads the counters the deployment added.  Also
the manifest as it stands since this cell was appended, which two older
tests of this directory describe as it stood before."""

import collections
import json
import pathlib

import pytest

from benchmarks import harness
from benchmarks.tests.tiny import run, tiny

CELL = "ec84-warp.scrub"
# the per-layer metrics this deployment brought, and the cells that
# list each
METRICS = {"scrub_pad_share.scrub": {CELL},
           "scrub_lane_kib.scrub": {CELL, "ec84-1m.scrub", "rep3-1m.scrub"},
           "parity_overhead_share.scrub": {CELL, "ec84-1m.scrub"}}
MANIFEST = json.loads((pathlib.Path(__file__).resolve().parents[2]
                       / "BENCHMARK.json").read_text())
# what `test_cells.py::test_the_control_is_not_correct` looks up in a
# dict of two cells: the count by which each cell's control has to fail
CONTROL_FAILS_BY = {"ec84-1m.scrub": "parity_wrong",
                    "rep3-1m.scrub": "plants_missed",
                    CELL: "parity_wrong"}


def tiny_sized(cell):
    """`tiny.tiny` with the store left to the law: 64 KiB blocks, 41
    objects of 128 B to 10 blocks, 3 MiB against a pool of 1 MiB."""
    kind = cell.kind
    store = dict(cell.config["store"], max_bytes=10 * 65536, objects=41,
                 bytes=3139689)
    store.pop("produced")
    tiny(cell)
    cell.config["store"] = store
    got = kind.classes(kind.law_sizes(store), 65536, 3072)
    assert min(got.values()) > 0, got
    return cell


def test_the_plan_is_the_files_law_whatever_the_seed():
    cell = harness.Cell(CELL)
    kind, store = cell.kind, cell.config["store"]
    block, inline = cell.config["block_size"], store["inline_threshold"]
    a, b = kind.sized_plan(store, 2**31 + 5), kind.sized_plan(store, 7)
    sizes = [n for _k, _i, n in a]
    assert collections.Counter(sizes) == collections.Counter(
        n for _k, _i, n in b)
    assert sizes != [n for _k, _i, n in b]      # the order is the seed's
    assert a == kind.sized_plan(store, 2**31 + 5)
    assert len(a) == store["objects"] == len({k for k, _i, _n in a})
    assert abs(sum(sizes) - store["bytes"]) <= 0.01 * store["bytes"]
    assert store["min_bytes"] <= min(sizes) and max(sizes) <= store[
        "max_bytes"]
    # as many objects in every doubling: 128 B to 10 MiB is 16.3 of them
    per_doubling = collections.Counter(n.bit_length() for n in sizes)
    assert max(per_doubling.values()) - min(
        v for d, v in per_doubling.items() if d < 24) <= 1
    got = kind.classes(sizes, block, inline)
    want = {k: v for k, v in store["produced"].items() if k in got}
    assert got == want and min(got.values()) > 0
    assert store["produced"]["block_files"] == (
        got["whole_block_files"] + got["short_block_files"])
    each = kind.one_of_each_class(a, block, inline)
    assert [n < inline for _k, _i, n in each] == [True, False, False]
    assert inline <= each[1][2] < block < each[2][2]


def test_the_program_is_correct_and_a_traced_run_reads_what_was_added():
    cell = tiny_sized(harness.Cell(CELL))
    res = run(cell, seed=2**31 + 17, trace=True)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == {"blocks_wrong", "plants_missed",
                                    "parity_wrong", "gets_wrong"}
    got = {name: res["metrics"][name]["value"] for name in METRICS}
    assert 0.0 < got["scrub_pad_share.scrub"] < 100.0
    assert 0.0 < got["scrub_lane_kib.scrub"] < 64.0     # some are short
    assert got["parity_overhead_share.scrub"] > 50.0    # members differ
    assert "scrub_parity_write_ms_per_gib" in res["metrics"]
    # the cell lists whatever `ec84-1m.scrub` lists, but the share of
    # the XLA scan: 926 lanes are 3 x 256 + 158, the tail is bucketed to
    # 256, and no batch of this store ever takes the scan
    listed = {m["name"] for m in cell.per_layer()}
    assert listed == (set(METRICS) | {
        m["name"] for m in harness.Cell("ec84-1m.scrub").per_layer()}) - {
            "xla_scan_compute_share.scrub"}


def test_a_cell_of_whole_blocks_reads_what_it_lists():
    """Every lane a whole block of tiny's 64 KiB, and codewords of equal
    members but for a heal's own; the cell without parity lists the one
    metric that needs none."""
    cell = tiny(harness.Cell("ec84-1m.scrub"))
    listed = {m for m, cells in METRICS.items() if "ec84-1m.scrub" in cells}
    assert listed == set(METRICS) & {m["name"] for m in cell.per_layer()}
    assert {"scrub_lane_kib.scrub"} == set(METRICS) & {
        m["name"] for m in harness.Cell("rep3-1m.scrub").per_layer()}
    res = run(cell, seed=2**31 + 29, trace=True)
    assert res["correct"], res["compared"]
    assert listed == set(METRICS) & set(res["metrics"])
    assert res["metrics"]["scrub_lane_kib.scrub"]["value"] == 64.0
    assert 50.0 <= res["metrics"][
        "parity_overhead_share.scrub"]["value"] < 100.0


def test_the_control_is_not_correct():
    cell = tiny_sized(harness.Cell(CELL))
    res = run(cell, seed=23, after_cluster=cell.kind.control(cell))
    assert not res["correct"], res["compared"]
    assert res["compared"][CONTROL_FAILS_BY[CELL]]["value"] > 0


def test_every_cell_has_a_count_its_control_fails_by():
    """`test_cells.py` runs every cell's control and holds it to be not
    correct; by which count, it can say for its two cells only."""
    assert set(CONTROL_FAILS_BY) == {w["name"] for w in MANIFEST["workloads"]}
    for name, count in CONTROL_FAILS_BY.items():
        parity = harness.Cell(name).config["codec"].get("store_parity")
        assert (count == "parity_wrong") == bool(parity)


def test_what_the_manifest_had_stands_as_it_was():
    """On the committed BENCHMARK.json, what `test_tracing_metrics.py`
    holds of the manifest as PR 26 left it: its nine metrics in their
    order right after what the benchmark had before them, each with its
    file, and the sidecar write's time in the cells that store parity
    and no other; then this PR's three, and nothing after."""
    from benchmarks.tests.test_tracing_metrics import NEW

    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index(NEW[0])
    assert first == 6 and names[first:first + len(NEW)] == NEW
    assert names[first + len(NEW):] == list(METRICS)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    stores_parity = [c for c in cells if harness.Cell(c).config["codec"].get(
        "store_parity")]
    assert by_name["scrub_parity_write_ms_per_gib"][
        "workloads"] == stores_parity == ["ec84-1m.scrub", CELL]
    for name in [*NEW, *METRICS]:
        assert by_name[name]["moves"] == "scrub_mib_s"
        assert (harness.HERE / "metrics" / f"{name}.json").is_file()
    for name, listing in METRICS.items():
        assert set(by_name[name]["workloads"]) == listing
    # a cell appended to a list stands last in it
    for metric in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        listed = metric.get("workloads", cells)
        assert listed == [c for c in cells if c in listed]


def test_a_sidecar_that_states_another_row_than_its_members_is_counted(
        tmp_path):
    """`rows_not_their_members`, on files made by hand: a row as long as
    its longest member passes; one padded to the block size, or whose
    lengths are not the files', does not."""
    import types

    import msgpack

    kind = harness.Cell(CELL).kind
    sizes = {"aa" + "0" * 62: 5000, "ab" + "1" * 62: 70000}
    for h, n in sizes.items():
        d = tmp_path / h[:2] / h[2:4]
        d.mkdir(parents=True)
        (d / h).write_bytes(bytes(n))
    st = types.SimpleNamespace(data_dir=str(tmp_path))

    def sidecar(name, maxlen, lengths):
        path = tmp_path / name
        path.write_bytes(msgpack.packb(
            {"hashes": [bytes.fromhex(h) for h in sizes], "maxlen": maxlen,
             "lengths": lengths}, use_bin_type=True))
        return str(path)

    good = sidecar("good.par", 70000, [5000, 70000])
    wide = sidecar("wide.par", 131072, [5000, 70000])
    other = sidecar("other.par", 70000, [70000, 70000])
    gone = str(tmp_path / "gone.par")       # the base kind's to count
    assert kind.rows_not_their_members(st, [good, gone]) == 0
    assert kind.rows_not_their_members(st, [good, wide, other]) == 2


def test_a_scrub_that_returns_every_block_as_good_is_seen():
    cell = tiny_sized(harness.Cell(CELL))

    def install(st):
        feeder = st.cluster.garages[st.node].block_manager.feeder
        real = feeder.scrub_async

        async def all_good(blocks, hashes, want_parity=True):
            ok, parity = await real(blocks, hashes, want_parity)
            return [True] * len(ok), parity

        feeder.scrub_async = all_good

    res = run(cell, seed=31, after_cluster=install)
    assert not res["correct"]
    assert res["compared"]["plants_missed"]["value"] > 0


def test_the_stall_stacks_are_written_under_the_interpreters_lock(tmp_path):
    """faulthandler's watchdog walks the threads' frames with no lock
    held and kills a busy process (PERF.md section 7); this kind's copy
    of `scrub_passes` is handed a stand-in that does not."""
    import faulthandler
    import time

    kind = harness.Cell(CELL).kind
    stalls = kind.base.faulthandler
    assert stalls is not faulthandler
    with open(tmp_path / "stalls.txt", "w+") as log:
        stalls.dump_traceback_later(0.05, file=log)
        stalls.dump_traceback_later(0.05, file=log)    # re-armed: one dump
        time.sleep(0.3)
        log.seek(0)
        stacks = log.read()
        assert stacks.count("Timeout (0.05 s)!") == 1
        assert "test_cells_sized.py" in stacks      # where this thread stood
        log.seek(0)
        log.truncate()
        stalls.dump_traceback_later(0.05, file=log)
        stalls.cancel_dump_traceback_later()
        time.sleep(0.2)
        log.seek(0)
        assert log.read() == ""
    stalls.dump_traceback_later(0.01, file=log)         # closed by the watch
    time.sleep(0.1)

"""Every cell end to end at a tiny size on the CPU: the program comes
out correct, its control does not, and a timed path broken underneath
is seen.  These drive `harness.run_cell` — everything of a run but the
command's look for a chip."""

import json
import pathlib

import pytest

from benchmarks import harness
from benchmarks.tests.tiny import run, tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_the_program_is_correct_and_reports_its_metrics(name):
    cell = tiny(harness.Cell(name))
    res = run(cell, seed=2**31 + 17)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    cell = tiny(harness.Cell(name))
    res = run(cell, seed=23, after_cluster=cell.kind.control(cell))
    assert not res["correct"], res["compared"]
    failing = {k for k, v in res["compared"].items()
               if v["value"] > v["limit"]}
    want = {"ec84-1m.scrub": "parity_wrong",
            "rep3-1m.scrub": "plants_missed"}[name]
    assert want in failing


def test_a_scrub_that_returns_every_block_as_good_is_seen():
    """The timed path broken underneath: the codec's verdicts altered
    where they are produced."""
    cell = tiny(harness.Cell("ec84-1m.scrub"))

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

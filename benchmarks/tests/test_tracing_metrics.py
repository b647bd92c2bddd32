"""The three readers of the program's own tracing (`counter_ratio`,
`timeline_args_share`, `timeline_union_share`) on hand-made windows,
and every metric file that names them through `Cell.read_per_layer` on
a traced run at a tiny size on the CPU."""

import json
import pathlib

import pytest

from benchmarks import harness
from benchmarks.tests.tiny import run, tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ["scrub_read_wait_ms_per_gib", "scrub_codec_wait_ms_per_gib",
       "scrub_other_ms_per_gib", "scrub_parity_write_ms_per_gib",
       "pool_compose_ms.scrub", "pool_adopt_ms.scrub",
       "compiles_per_pass.scrub", "xla_scan_compute_share.scrub",
       "device_unoccupied_share.scrub"]


def reader(name):
    return harness.load_module(
        harness.HERE / "readers" / f"{name}.py", f"bench_reader_{name}")


def window(before, after, timeline=(), t0=0, t1=10_000_000):
    return {"before": {"metrics": before, "mono_us": t0},
            "after": {"metrics": after, "mono_us": t1},
            "timeline": list(timeline)}


SEG = 'scrub_pass_seconds_total{segment="%s"}'


def test_counter_ratio_divides_growth_by_growth():
    read = reader("counter_ratio").read
    win = window(
        {SEG % "read_wait": 1.0, SEG % "other": 5.0,
         "scrub_verified_bytes_total": 2.0**30},
        {SEG % "read_wait": 1.5, SEG % "other": 5.0,
         "scrub_verified_bytes_total": 3 * 2.0**30})
    got = read(win, [["scrub_pass_seconds_total", {"segment": "read_wait"}]],
               [["scrub_verified_bytes_total", {}]], scale=1000.0 * 2**30)
    assert got == {"value": 250.0, "samples": 2**31}
    # a numerator that stood still beside a denominator that moved: 0
    zero = read(win, [["scrub_pass_seconds_total", {"segment": "other"}]],
                [["scrub_verified_bytes_total", {}]])
    assert zero["value"] == 0.0
    # without a denominator: the growth itself
    assert read(win, [["scrub_verified_bytes_total", {}]])["value"] == 2.0**31


@pytest.mark.parametrize("before, after", [
    ({}, {}),                                           # the parent's run
    ({"scrub_passes_total": 4.0}, {"scrub_passes_total": 4.0}),
    ({"codec_compiles_total{from=\"built\",where=\"compose\"}": 1.0},
     {"codec_compiles_total{from=\"built\",where=\"compose\"}": 8.0}),
])
def test_counter_ratio_reads_nothing_where_the_denominator_stood(before,
                                                                 after):
    read = reader("counter_ratio").read
    assert read(window(before, after), [["codec_compiles_total", {}]],
                [["scrub_passes_total", {}]]) is None
    assert read(window(before, before),
                [["scrub_passes_total", {}]]) is None


def test_compiles_per_pass_is_zero_where_nothing_compiled():
    spec = harness.load_json(
        harness.HERE / "metrics" / "compiles_per_pass.scrub.json")
    read = reader(spec["reader"]).read
    quiet = window({"scrub_passes_total": 2.0}, {"scrub_passes_total": 5.0})
    assert read(quiet, **spec["params"]) == {"value": 0.0, "samples": 3}
    series = 'codec_compiles_total{from="%s",where="%s"}'
    busy = window(
        {"scrub_passes_total": 2.0, series % ("cache", "compose"): 3.0},
        {"scrub_passes_total": 4.0, series % ("cache", "compose"): 10.0,
         series % ("built", "pool adopt"): 7.0})
    assert read(busy, **spec["params"])["value"] == 7.0


def ev(name, ts, dur, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": args}


def test_timeline_args_share():
    read = reader("timeline_args_share").read
    tl = [ev("compute scrub", 0, 300, variant="xla", lanes=64),
          ev("compute scrub", 400, 100, variant="pallas", lanes=256),
          ev("compute hash", 600, 900, variant=None),
          ev("submit scrub", 0, 50, variant="xla")]
    got = read(window({}, {}, tl), "compute scrub", "variant", "xla")
    assert got == {"value": 75.0, "samples": 2}
    # every batch on the other kernel: a share of 0, not nothing
    assert read(window({}, {}, tl[1:]), "compute scrub", "variant",
                "xla")["value"] == 0.0
    # the parent's events carry no variant: nothing to read
    bare = [ev("compute scrub", 0, 300, prefetch=False)]
    assert read(window({}, {}, bare), "compute scrub", "variant",
                "xla") is None
    assert read(window({}, {}), "compute scrub", "variant", "xla") is None


def test_timeline_union_share():
    read = reader("timeline_union_share").read
    tl = [ev("compute scrub", 100, 200), ev("compute scrub", 250, 150),
          ev("compute hash", 900, 300),      # clipped at the window's end
          ev("collect scrub", 0, 1000)]
    win = window({}, {}, tl, t0=0, t1=1000)
    # union: [100, 400) and [900, 1000) of 1000
    assert read(win, "compute ")["value"] == pytest.approx(40.0)
    got = read(win, "compute ", uncovered=True)
    assert got["value"] == pytest.approx(60.0) and got["samples"] == 3
    assert read(window({}, {}, tl[3:], t1=1000), "compute ") is None
    assert read(window({}, {}, tl, t0=5, t1=5), "compute ") is None


def test_every_new_metric_is_in_the_manifest_with_its_file():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert by_name[name]["moves"] == "scrub_mib_s"
        spec = harness.load_json(harness.HERE / "metrics" / f"{name}.json")
        assert hasattr(reader(spec["reader"]), "read")
    assert by_name["scrub_parity_write_ms_per_gib"]["workloads"] == [
        "ec84-1m.scrub"]
    # appended: what the benchmark had stands first, as it was
    assert [m["name"] for m in MANIFEST["per_layer"]][-len(NEW):] == NEW


def test_a_traced_run_reads_every_new_metric():
    """Through `Cell.read_per_layer`, on the program's own counters and
    ring, in the cell that reports all nine.  The numbers are a CPU's
    and mean nothing; that each is read, and none is None, is the point."""
    cell = tiny(harness.Cell("ec84-1m.scrub"))
    res = run(cell, seed=2**31 + 29, trace=True)
    assert res["correct"], res["compared"]
    assert set(NEW) <= set(res["metrics"])
    for metric in NEW:
        assert res["metrics"][metric]["value"] >= 0.0
    assert 0.0 <= res["metrics"]["device_unoccupied_share.scrub"][
        "value"] <= 100.0
    assert res["metrics"]["scrub_codec_wait_ms_per_gib"]["value"] > 0
    # the cell without stored parity reports the other eight
    plain = {m["name"] for m in harness.Cell("rep3-1m.scrub").per_layer()}
    assert set(NEW) - plain == {"scrub_parity_write_ms_per_gib"}

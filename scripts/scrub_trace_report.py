#!/usr/bin/env python3
"""What one traced benchmark run's `.xplane.pb` holds of the program's
own tracing, beside the ring it was stamped from:

    python3 scripts/scrub_trace_report.py --workload rep3-1m.scrub --seed 7

Runs the cell with `--trace 1` through the benchmark's harness (so it
needs the chip, like `benchmarks/run.py`), keeps the profiler's trace
and the node's `device_timeline` ring past the run, and reports

  - every `gt:clock` annotation: `start_ns − mono_ns`, the offset from
    the ring's clock to the profiler's;
  - the `gt:<name>` spans by name, and for those that are also ring
    events (`compose`, `pool adopt`) the residual of each against its
    ring event after the offset is taken out: how stable the offset is
    over the window;
  - the program names on the device's "XLA Modules" line, and which of
    the named scopes (`blake2s_scan`, `gf_apply`, `pool_compose`,
    `pool_adopt`) show in the op events' stats;
  - the link profiler's scrub stages and the two stamped sections inside
    them, cumulative over the process: how much of `adopt` is `compose`
    and of `collect` is `pool adopt`; and the compile listener's
    counters by `where` and `from`;
  - every `scrub pass` the ring holds: its span beside the sum of its
    segments, and its `other`; and beside it the I/O lane's own account
    of the pass (`lane`): its `read files` events summed, the lane's
    critical path (`wall_ms`) and its stages, whose slices' part sums
    to the slices' walls (`slices_ms`).

The benchmark's own reduction (`benchmarks/trace_reduce.py`) reads none
of this yet; this script is how PERF.md's record of it was made.  The
last line of standard output is the report as JSON; the harness's result
line stands before it.
"""

import argparse
import collections
import glob
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

T_START = time.monotonic()
REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SCOPES = ("blake2s_scan", "gf_apply", "pool_compose", "pool_adopt")
OPS_SAMPLED = 400_000     # op events looked at for scope names


def keep_ring(kind, kept: dict):
    """The node under test's ring and its stage seconds, taken just
    before the cluster stops."""
    shutdown = kind.shutdown

    async def keeping(state):
        from benchmarks.cluster import metric_sum

        obs = state.cluster.garages[state.node].block_manager.codec.obs
        kept["ring"] = obs.timeline.snapshot()
        stages = obs.link_profiler.summary(by_kind=True)
        metrics = state.cluster.admins[state.node].metrics()
        kept["stages"] = {
            "scrub": {s: {"count": v["count"], "seconds": v["seconds"]}
                      for s, v in stages.get("by_kind", {}).get(
                          "scrub", {}).items()},
            **{stage: {
                "count": metric_sum(metrics,
                                    "transport_substage_calls_total",
                                    stage=stage),
                "seconds": metric_sum(metrics,
                                      "transport_substage_seconds_total",
                                      stage=stage)}
               for stage in ("compose", "pool_adopt")}}
        kept["compiles"] = {series: v for series, v in metrics.items()
                            if series.startswith("codec_compile")}
        await shutdown(state)

    kind.shutdown = keeping


def report(trace_dir: str, ring: list) -> dict:
    import jax.profiler

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(paths[-1])
    marks, modules, scoped = [], collections.Counter(), {}
    stat_keys = collections.Counter()
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == "XLA Modules":
                modules.update(e.name.split("(")[0] for e in line.events)
            elif device and line.name == "XLA Ops":
                for i, e in enumerate(line.events):
                    if i >= OPS_SAMPLED:
                        break
                    stats = dict(e.stats)
                    stat_keys.update(stats)
                    text = " ".join(str(v) for v in stats.values())
                    for scope in SCOPES:
                        if scope in text and scope not in scoped:
                            scoped[scope] = {"op": e.name[:80], "stats": {
                                k: str(v)[:160] for k, v in stats.items()
                                if scope in str(v)}}
            elif not device:
                marks += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                          for e in line.events if e.name.startswith("gt:")]
    clocks = [(s, st["mono_ns"]) for n, s, _d, st in marks
              if n == "gt:clock" and "mono_ns" in st]
    out = {"gt_spans": dict(collections.Counter(n for n, *_ in marks)),
           "gt_clock_offsets_ns": [s - m for s, m in clocks],
           "xla_modules": dict(modules),
           "scopes_in_op_stats": scoped,
           "op_stat_keys": sorted(stat_keys)}
    if not clocks:
        return out
    offset = clocks[0][0] - clocks[0][1]
    t_lo = min(s for _n, s, _d, _st in marks) - offset
    for name in ("compose", "pool adopt"):
        prof = sorted(s for n, s, _d, _st in marks if n == f"gt:{name}")
        # ring events of the traced stretch, by order of their stamps
        evs = sorted(e["ts"] for e in ring if e["name"] == name
                     and e["ts"] * 1000 >= t_lo - 1_000_000)
        res = [(p - offset) / 1e3 - ts for p, ts in zip(prof, evs)]
        if res:
            out[f"residual_us:{name}"] = {
                "n": len(res), "ring_events": len(evs), "min": min(res),
                "median": statistics.median(res), "max": max(res)}
    return out


def lane_account(ring: list, root: dict) -> dict:
    """The I/O lane's account of the pass whose `scrub pass` event is
    `root`: the `read files` events that began inside it, summed.
    `wall_ms` beside the pass's span says whether the lane bounds it;
    `unaccounted_us` is the slices' walls less their six stages, which
    the ring's rounding alone keeps from 0."""
    from garage_tpu.block.repair import SCRUB_IO_STAGES

    evs = [e for e in ring if e["name"] == "read files"
           and root["ts"] <= e["ts"] <= root["ts"] + root["dur"]]
    stages = [f"{s}_ms" for s in SCRUB_IO_STAGES]
    acct = {k: round(sum(e["args"].get(k, 0) for e in evs), 3)
            for k in stages + ["slices_ms", "cpu_ms", "direct", "buffered",
                               "bytes"]}
    acct["batches"] = len(evs)
    acct["wall_ms"] = round(sum(e["dur"] for e in evs) / 1e3, 3)
    acct["unaccounted_us"] = round(1e3 * (acct["slices_ms"] - sum(
        acct[k] for k in stages[1:])), 1)        # all but `list`
    return acct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    from benchmarks import harness, run as bench_run

    cell = harness.Cell(args.workload)
    bench_run.configure_compile_cache()
    kept: dict = {}
    keep_ring(cell.kind, kept)
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="garage_tpu_trace_"))
    try:
        ctx = harness.Ctx(cell, args.seed, args.seconds, True, tmp, T_START)
        ctx.compiles.listen()
        result = harness.run_blocking(ctx)
        harness.print_result(result)
        rep = report(str(tmp / "trace"), kept.get("ring", []))
        rep["stages_cumulative"] = kept.get("stages")
        rep["compiles_cumulative"] = kept.get("compiles")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    # every pass the ring holds: its account against its span
    rep["passes"] = [
        {"span_us": e["dur"],
         "segments_us": round(sum(v for k, v in e["args"].items()
                                  if k.endswith("_ms")) * 1e3, 1),
         "other_ms": e["args"].get("other_ms", 0.0),
         "lane": lane_account(kept["ring"], e)}
        for e in kept.get("ring", []) if e["name"] == "scrub pass"]
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The control of a cell, on the chip, at the cell's own size:

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 10

It puts the cell's reference, with one guarantee of its configuration
broken, in the program's place (configs/<config>.reference.py says
which, the cell's kind where: its `control(cell)`) and has to come out
not correct on every seed.  A benchmark run never comes here.  One line
a seed, then a summary.
"""

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import json         # noqa: E402
import pathlib      # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def run_one(cell, seed: int, seconds: float, t_start: float) -> dict:
    from benchmarks import harness

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="garage_tpu_control_"))
    try:
        ctx = harness.Ctx(cell, seed, seconds, False, tmp, t_start)
        ctx.after_cluster = cell.kind.control(cell)
        return harness.run_blocking(ctx)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmarks import harness
    from benchmarks.run import configure_compile_cache

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(f"benchmarks: no TPU here ({dev.platform})\n")
        return 2
    configure_compile_cache()
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = harness.Cell(args.workload)
        res = run_one(cell, seed, args.seconds, time.monotonic())
        row = {"workload": args.workload, "seed": seed,
               "correct": res["correct"],
               "compared": {k: v["value"]
                            for k, v in res["compared"].items()},
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    ok = not any(r["correct"] for r in rows)
    print(f"SUMMARY {args.workload} control: "
          f"{sum(r['correct'] for r in rows)} of {len(rows)} correct; "
          f"{'as it has to be' if ok else 'NOT as it has to be'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

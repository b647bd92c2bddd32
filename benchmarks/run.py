#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip: the deployment's nodes in-process,
one S3 endpoint, the client on the same event loop.  Exits non-zero and
prints no result where JAX finds no TPU or fewer chips than the cell
asks for.  The last line of standard output is the result object.
"""

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse     # noqa: E402
import pathlib      # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def scratch_dir() -> pathlib.Path:
    """The run's stores and trace: under TMPDIR, which the driver gives
    each side for itself; made anew by every run."""
    import tempfile

    return pathlib.Path(tempfile.mkdtemp(prefix="garage_tpu_bench_"))


def configure_compile_cache() -> str:
    """The program's own choice of directory (JAX_COMPILATION_CACHE_DIR
    if set, else `<checkout>/.jax_cache`), and every program kept, not
    only those that compile for a second or more: the scrub road runs
    hundreds of small eager programs that each process compiled anew."""
    import jax

    from garage_tpu.ops.compile_cache import ensure_compile_cache

    path = ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.Cell(args.workload)

    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        sys.stderr.write(f"benchmarks: {cell.name} needs {cell.chips} TPU "
                         f"chip(s); JAX found {found}\n")
        return 2
    cache = configure_compile_cache()    # needs the program: fails first
    harness.log(f"device: {found}")
    harness.log(f"compile cache: {cache}")

    tmp = scratch_dir()
    try:
        ctx = harness.Ctx(cell, args.seed, args.seconds, bool(args.trace),
                          tmp, T_START)
        ctx.compiles.listen()
        result = harness.run_blocking(ctx)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A cell cut to a size a test run can hold, through the harness's own
functions (the command itself refuses a machine without a TPU)."""

import pathlib
import shutil
import tempfile
import time

from benchmarks import harness


def tiny(cell):
    """64 KiB blocks; the store is 3x the pool and a batch is the pool,
    as 576 blocks, 256 MiB and 256 lanes are at full size."""
    cell.config["block_size"] = 65536
    cell.config["store"] = {"small_objects": 40, "big_objects": 1,
                            "big_object_blocks": 8}
    cell.config["codec"] = dict(cell.config["codec"], pool_mib=1,
                                batch_blocks=16)
    return cell


def run(cell, seed=1, seconds=2.0, trace=False, after_cluster=None):
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench_test_"))
    try:
        ctx = harness.Ctx(cell, seed, seconds, trace, tmp, time.monotonic())
        ctx.after_cluster = after_cluster
        return harness.run_blocking(ctx)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

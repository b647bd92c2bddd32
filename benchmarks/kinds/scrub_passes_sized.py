"""Traffic kind `scrub_passes_sized`: the passes of `scrub_passes` over
a store of objects as clients write them, whose sizes follow a law the
configuration states, in place of a plan of whole blocks.

Mix parameters: those of `scrub_passes`, which this kind loads and
hands `setup`, `window`, `check`, `control` and `shutdown` to.  What it
puts in that kind's way is the plan and the places where the plan was
taken to be whole blocks: the count of block files set-up waits for,
the ids the store has to hold after the window (an object under
`inline_threshold` never reaches the block layer), the widths a heal
encodes at, which two warm passes do not all meet (`warm_heal_widths`),
and the width of a sidecar's row, which `check` holds to its members'
files.  The window's notes name the programs compiled inside it.  The
stacks that kind writes when the event loop stands still are written
here by a thread that holds the interpreter's lock (`StallStacks`).

The configuration's `store`:
    law               "log2-uniform": as many objects in every doubling
    min_bytes, max_bytes, objects
                      the sizes are the (i + 1/2) / objects quantiles of
                      the law on [min_bytes, max_bytes]: the same in
                      every run, handed to the keys in an order drawn
                      from the seed
    bytes             what the sizes sum to (checked to 1%)
    inline_threshold  the product's: an object below it is kept in its
                      table entry and has no block file
A store given as whole blocks (`scrub_passes`'s form, to which
`tests/tiny.py` cuts every cell) is run as that kind runs it.
"""

import asyncio
import math
import os
import pathlib
import sys
import threading
import traceback

import numpy as np

from benchmarks import cluster as cl
from benchmarks import harness

base = harness.load_module(
    pathlib.Path(__file__).with_name("scrub_passes.py"),
    "bench_kind_scrub_passes_under_sized")
control, shutdown = base.control, base.shutdown
WHOLE_BLOCKS = (base.object_plan, base.expected_ids, base.cl)


class StallStacks:
    """`faulthandler`'s two calls as `scrub_passes.Watch` makes them.
    faulthandler's own watchdog thread reads every thread's frames
    without the interpreter's lock, and blocks all signals: where other
    threads run Python meanwhile (a traced pass of this cell outlasts
    TRACE_S, so the profiler's hand-over always stalls the loop in the
    middle of one) CPython 3.12 dies of a segmentation fault that no
    handler sees: the exit code 139 of PERF.md section 7.  This one
    waits the same way and then asks the interpreter, under its lock,
    where the threads are."""

    def __init__(self):
        self._cancel = None

    def dump_traceback_later(self, timeout: float, file) -> None:
        self.cancel_dump_traceback_later()
        cancel = self._cancel = threading.Event()

        def wait():
            if cancel.wait(timeout):
                return
            stacks = "".join(
                f"Thread 0x{ident:016x} (most recent call last):\n"
                + "".join(traceback.format_stack(frame))
                for ident, frame in sys._current_frames().items())
            try:
                file.write(f"Timeout ({timeout} s)!\n{stacks}\n")
                file.flush()
            except ValueError:      # the watch was stopped and closed it
                pass

        threading.Thread(target=wait, name="stall-stacks",
                         daemon=True).start()

    def cancel_dump_traceback_later(self) -> None:
        if self._cancel is not None:
            self._cancel.set()


base.faulthandler = StallStacks()


def law_sizes(store: dict):
    """The store's object sizes, ascending: quantiles, not draws, so
    that every seed scrubs the same bytes in the same number of lanes."""
    if store["law"] != "log2-uniform":
        raise ValueError(f"no such law of sizes: {store['law']!r}")
    lo, hi = math.log2(store["min_bytes"]), math.log2(store["max_bytes"])
    n = store["objects"]
    sizes = [int(2.0 ** (lo + (i + 0.5) / n * (hi - lo))) for i in range(n)]
    if abs(sum(sizes) - store["bytes"]) > 0.01 * store["bytes"]:
        raise ValueError(f"the law's sizes sum to {sum(sizes)}, the "
                         f"configuration states {store['bytes']}")
    return sizes


def classes(sizes, block: int, inline: int) -> dict:
    """What the sizes come to on a node's disk."""
    stored = [n for n in sizes if n >= inline]
    return {"inline_objects": len(sizes) - len(stored),
            "one_block_objects": sum(n <= block for n in stored),
            "multi_block_objects": sum(n > block for n in stored),
            "whole_block_files": sum(n // block for n in stored),
            "short_block_files": sum(n % block > 0 for n in stored),
            "block_bytes": sum(stored)}


def sized_plan(store: dict, seed: int):
    """[(key, index, bytes)]: the law's sizes over the keys in an order
    drawn from the seed (an object's bytes come from (seed, index))."""
    sizes = law_sizes(store)
    order = np.random.default_rng([seed, 13]).permutation(len(sizes))
    return [(f"obj/{i:04d}", i, sizes[int(j)]) for i, j in enumerate(order)]


class SizedCluster:
    """`benchmarks.cluster` as the base kind sees it, but for the count
    of block files set-up waits for on every node: the base counts
    bytes // block_size, and here nearly every object ends in a short
    block and the smallest have none."""

    def __init__(self, block_files: int):
        self.want = block_files

    def __getattr__(self, name):
        return getattr(cl, name)

    async def wait_blocks(self, data_dirs, _whole_blocks, **kw):
        await cl.wait_blocks(data_dirs, self.want, **kw)


async def setup(ctx):
    store, block = ctx.config["store"], ctx.config["block_size"]
    base.object_plan, base.expected_ids, base.cl = WHOLE_BLOCKS
    if "law" in store:
        inline = store["inline_threshold"]
        plan = sized_plan(store, ctx.seed)
        got = classes([n for _k, _i, n in plan], block, inline)
        harness.log(f"sized store: {len(plan)} objects, "
                    f"{sum(n for _k, _i, n in plan)} bytes: {got}")
        stored = [p for p in plan if p[2] >= inline]
        expected_ids = WHOLE_BLOCKS[1]
        base.object_plan = lambda _store, _block: plan
        base.expected_ids = lambda _plan, seed, blk: expected_ids(
            stored, seed, blk)
        base.cl = SizedCluster(got["whole_block_files"]
                               + got["short_block_files"])
    st = await base.setup(ctx)
    if "law" in store and st.parity:
        # a program from before PR 29's counters views an encode's words
        # as bytes on the device, and the chip's compiler takes minutes
        # over that at every new width: it is left as it was measured
        if cl.metric_sum(st.admin.metrics(), "parity_sidecar_bytes_total"):
            with ctx.setup_item("warm widths"):
                await asyncio.to_thread(warm_heal_widths, st,
                                        store["inline_threshold"], block)
        else:
            harness.log("heal widths not warmed: the program lacks "
                        "parity_sidecar_bytes_total")
    return st


def warm_heal_widths(st, shortest: int, block: int) -> None:
    """A healed block goes back through the write-time parity
    accumulator, which encodes its codeword of one or two members on the
    device at the row width of the block's length (a power of two, from
    the shortest block file's to `block_size`'s).  Two warm passes heal
    four blocks; the window's twelve met widths new to the process, and
    each cost its pass a program or more under `submit encode` (PERF.md,
    PR 29).  So set-up encodes one block at every such width, through
    the feeder call the accumulator makes."""
    feeder = st.cluster.garages[st.node].block_manager.feeder
    width = 1 << (shortest - 1).bit_length()
    while width < block:
        feeder.encode_or_direct([bytes(width)])
        width <<= 1
    feeder.encode_or_direct([bytes(block)])


async def window(ctx, st, seconds: float) -> dict:
    """`scrub_passes`'s window.  Its notes also put every program the
    process built or loaded inside it to the span it was compiled under
    (`codec_compiles_total{where, from}`, which the program counts in an
    untraced run too): lanes of many widths meet new shapes more often
    than whole blocks do, and a slow window has to say which step did."""
    def compiles():
        return {s: v for s, v in st.admin.metrics().items()
                if s.startswith(("codec_compiles_total{",
                                 "codec_compile_seconds_total{"))}

    before = compiles()
    win = await base.window(ctx, st, seconds)
    grown = {s: round(v - before.get(s, 0.0), 3)
             for s, v in sorted(compiles().items())
             if v > before.get(s, 0.0)}
    win["notes"].append("programs compiled or loaded inside the window, by "
                        f"the span they ran under: {grown or 'none'}")
    return win


def one_of_each_class(plan, block: int, inline: int):
    """The largest inline object, the longest object of one short block
    and the largest object: what a read-back has to cover whatever the
    base kind's sample drew."""
    groups = ([p for p in plan if p[2] < inline],
              [p for p in plan if inline <= p[2] < block],
              [p for p in plan if p[2] > block])
    return [max(g, key=lambda p: p[2]) for g in groups if g]


def rows_not_their_members(st, sidecars) -> int:
    """Of the sidecars the base kind compared, those whose row is not
    its members': `maxlen` other than the longest member file's length,
    or `lengths` other than the files' own.  The base computes the
    reference's parity at the width the sidecar states, and where every
    block is whole that is the block size; here the row's width is what
    the deployment added, and a sidecar that states too long a row (and
    so holds parity bytes that cover nothing) would pass.  A sidecar
    that was not written again, or names a block the store lacks, is the
    base's to count."""
    by_hash = dict(cl.block_files(st.data_dir))
    wrong = 0
    for path in sidecars:
        if not os.path.exists(path):
            continue
        man = base.read_sidecar(path)
        members = [bytes(h).hex() for h in man["hashes"]]
        if any(h not in by_hash for h in members):
            continue
        sizes = [os.path.getsize(by_hash[h]) for h in members]
        if man["maxlen"] != max(sizes) or list(man["lengths"]) != sizes:
            print(f"sidecar's row is not its members': {path}: maxlen "
                  f"{man['maxlen']}, lengths {list(man['lengths'])}, the "
                  f"files' {sizes}", flush=True)
            wrong += 1
    return wrong


async def check(ctx, st, win: dict) -> dict:
    compared = await base.check(ctx, st, win)
    store = ctx.config["store"]
    if "parity_wrong" in compared:
        compared["parity_wrong"]["value"] += await asyncio.to_thread(
            rows_not_their_members, st,
            [p for ps in win["passes"] for p in ps["removed"]])
    if "law" in store:
        for key, idx, n in one_of_each_class(
                st.plan, ctx.config["block_size"], store["inline_threshold"]):
            status, _h, body = await st.s3.req("GET", f"/{base.BUCKET}/{key}")
            compared["gets_wrong"]["value"] += not (
                status == 200 and body == cl.object_bytes(ctx.seed, idx, n))
    return compared

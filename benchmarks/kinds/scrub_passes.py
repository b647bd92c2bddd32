"""Traffic kind `scrub_passes`: back-to-back operator-started scrub
passes on one node of a loaded store, with media faults planted from
the seed before every pass.

Mix parameters (`traffic/<mix>.json`), what a second mix would vary:
    node_under_test    index of the node that scrubs and owns the chip
    corrupt_per_pass   block files that get one byte flipped before a pass
    sidecars_per_pass  parity sidecars removed before a pass (only where
                       the configuration stores parity)

The window: plant, start a pass (`launch_repair scrub start`), wait for
its end, again until `--seconds` have passed and the pass in flight has
ended.  The rate is every byte of block data on the node at the start
of each pass over all of that time, gaps included.  What a pass's
faults are drawn from (the block files with their sizes, the scrub's
sidecars with their members) is listed once, in set-up: inside the
window planting is two byte flips, four unlinks and a few stats.
"""

import asyncio
import faulthandler
import gc
import os
import pathlib
import time
import types

import numpy as np

from benchmarks import arith
from benchmarks import cluster as cl
from benchmarks.s3client import S3Client

BUCKET = "bench"
POLL_S = 0.002      # a pass is seconds long: 2 ms is 0.05% of it
WARM_PASS = 10**6   # the warm-up's passes draw other faults
# Passes in set-up, faults and all, on the node under test only: the
# first compiles this cell's lane buckets and its heal path on an empty
# pool, the second the programs that put a batch together from pool
# pages, which only a filled pool runs (24 compilations inside the
# window with one, 0 with two; PERF.md, PR 25).
WARM_PASSES = 2
LOAD_CONCURRENCY = 32   # PUTs in flight while the store is loaded
GET_SAMPLE = 12         # objects read back through S3 after the window
# The profiler takes 30 us to hand over each device event and a pass is
# 3.7 million of them: a traced run's window is short, and its profile
# holds the window's last pass, whole (PERF.md section 3).
TRACED_WINDOW_S = 20.0
TRACE_S = 8.0


def object_plan(store: dict, block: int):
    plan = [(f"small/{i:04d}", i, block)
            for i in range(store["small_objects"])]
    plan += [(f"big/{i:02d}", store["small_objects"] + i,
              store["big_object_blocks"] * block)
             for i in range(store["big_objects"])]
    return plan


def sidecar_files(data_dir: str):
    return sorted(str(p) for p in
                  pathlib.Path(data_dir, "parity").rglob("*.par"))


def read_sidecar(path: str) -> dict:
    import msgpack

    with open(path, "rb") as f:
        return msgpack.unpackb(f.read(), raw=False)


def sidecar_members(path: str):
    """The member ids of a sidecar, in codeword order.  The manifest
    names them before the parity, so the head of the file holds them;
    were that to change, the whole file is read."""
    import msgpack

    with open(path, "rb") as f:
        head = msgpack.Unpacker(raw=False)
        head.feed(f.read(4096))
    try:
        for _ in range(head.read_map_header()):
            if head.unpack() == "hashes":
                return [bytes(h).hex() for h in head.unpack()]
            head.skip()
    except msgpack.OutOfData:
        pass
    return [bytes(h).hex() for h in read_sidecar(path)["hashes"]]


def survey_blocks(st) -> None:
    """Set-up's listing of the block files of the node under test, with
    their sizes: what every pass's flips are drawn from.  A quarantined
    block comes back under its own name, so the list holds."""
    st.files = cl.block_files(st.data_dir)
    st.sizes = [os.path.getsize(p) for _h, p in st.files]
    st.size_of = {h: n for (h, _p), n in zip(st.files, st.sizes)}
    st.store_bytes = sum(st.sizes)


def survey_sidecars(st) -> None:
    """Set-up's listing of the scrub's own sidecars, with their members:
    k of them in id order (a write-time codeword, which a heal also
    writes, groups by arrival and is never the scrub's to write again).
    Run after a warm pass; a sidecar read before is not read again."""
    for path in sidecar_files(st.data_dir):
        if path not in st.sidecar_members:
            st.sidecar_members[path] = sidecar_members(path)
    st.sidecars = [(path, frozenset(members))
                   for path, members in sorted(st.sidecar_members.items())
                   if len(members) == st.rs_data
                   and members == sorted(members)]


def plant(st, seed: int, pass_no: int, since: float) -> dict:
    """The faults of one pass, drawn from (seed, pass number) out of
    set-up's listings: flip one byte in `corrupt_per_pass` block files,
    and remove `sidecars_per_pass` of the scrub's sidecars that the last
    pass refreshed, none that covers a block just corrupted, since its
    row is not re-encoded before the block is healed.  Returns what was
    planted and the bytes of block data now on the node."""
    mix = st.mix
    rng = np.random.default_rng([seed, 7, pass_no])
    pending = {h for h, p in st.victims if not os.path.exists(p)}
    victims = []
    for i in rng.permutation(len(st.files)):
        if len(victims) >= mix["corrupt_per_pass"]:
            break
        h, path = st.files[int(i)]
        if h in pending:
            continue
        off = int(rng.integers(0, st.sizes[int(i)]))
        with open(path, "r+b") as f:
            f.seek(off)
            b = f.read(1)
            f.seek(off)
            f.write(bytes([b[0] ^ 0x40]))
        victims.append((h, path))
    removed = []
    if st.parity and not pending:
        hit = {h for h, _p in victims}
        for i in rng.permutation(len(st.sidecars)):
            if len(removed) >= mix["sidecars_per_pass"]:
                break
            path, members = st.sidecars[int(i)]
            if not hit.isdisjoint(members):
                continue
            try:
                fresh = os.path.getmtime(path) >= since
            except FileNotFoundError:
                continue
            if fresh:
                os.remove(path)
                removed.append(path)
    st.victims += victims
    return {"victims": victims, "removed": removed,
            "blocks": len(st.files) - len(pending),
            "bytes": st.store_bytes - sum(st.size_of[h] for h in pending)}


class Watch:
    """What held the event loop or the interpreter during a pass: the
    longest overshoot of a 50 ms sleep, and the seconds spent in full
    collections; and where every thread stood whenever the loop did not
    come round for STALL_S (faulthandler's timer needs no GIL).  For
    the earlier lines of a run, so that a pass that took long says why."""

    EVERY_S = 0.05
    STALL_S = 1.5

    def __init__(self, log_path):
        self.lag_s = self.gc_s = 0.0
        self._gc_t0 = None
        self._log = open(log_path, "w+")
        self._task = asyncio.ensure_future(self._beat())
        gc.callbacks.append(self._on_gc)

    async def _beat(self):
        beats = 0
        while True:
            if beats % 10 == 0:     # every 0.5 s: arming starts a thread
                faulthandler.dump_traceback_later(self.STALL_S,
                                                  file=self._log)
            beats += 1
            t = time.monotonic()
            await asyncio.sleep(self.EVERY_S)
            self.lag_s = max(self.lag_s,
                             time.monotonic() - t - self.EVERY_S)

    def _on_gc(self, phase, info):
        if info["generation"] < 2:
            return
        if phase == "start":
            self._gc_t0 = time.monotonic()
        elif self._gc_t0 is not None:
            self.gc_s += time.monotonic() - self._gc_t0
            self._gc_t0 = None

    def take(self) -> dict:
        out = {"loop_lag_ms": round(self.lag_s * 1e3, 1),
               "gc_ms": round(self.gc_s * 1e3, 1)}
        self.lag_s = self.gc_s = 0.0
        return out

    def stop(self) -> str:
        """→ the stacks written while the loop stood still, if any."""
        faulthandler.cancel_dump_traceback_later()
        gc.callbacks.remove(self._on_gc)
        self._task.cancel()
        self._log.seek(0)
        stacks = self._log.read()
        self._log.close()
        return stacks


def one_pass(st):
    return cl.scrub_pass(st.cluster, st.node, POLL_S)


async def setup(ctx):
    st = types.SimpleNamespace()
    st.mix, config = ctx.mix, ctx.config
    with ctx.setup_item("build"):
        cl.build_native()
    with ctx.setup_item("cluster"):
        st.cluster = await cl.start_cluster(config, ctx.tmp)
    st.cluster_up = time.monotonic()
    st.node = st.mix["node_under_test"]
    st.admin = st.cluster.admins[st.node]
    st.data_dir = st.cluster.data_dirs[st.node]
    st.parity = bool(config["codec"].get("store_parity"))
    st.victims, st.sidecar_members, st.sidecars = [], {}, []
    st.rs_data = config["rs_data"]
    if ctx.after_cluster is not None:
        ctx.after_cluster(st)
    import aiohttp

    st.session = aiohttp.ClientSession()
    st.s3 = S3Client(st.session, st.cluster.server.port, st.cluster.key_id,
                     st.cluster.secret)
    st.plan = object_plan(config["store"], config["block_size"])
    blocks = sum(n // config["block_size"] for _k, _i, n in st.plan)
    with ctx.setup_item("load"):
        await cl.load_objects(st.s3, BUCKET, st.plan, ctx.seed,
                              LOAD_CONCURRENCY)
    with ctx.setup_item("settle"):
        await cl.wait_blocks(st.cluster.data_dirs, blocks)
        await asyncio.to_thread(survey_blocks, st)
    # the operator's throttle (`worker set scrub-tranquility`), as the
    # configuration's `assumed` states it: changes no route and no gate
    await st.admin.cmd("worker_set_var", var="scrub-tranquility",
                       value=str(config["assumed"]["scrub_tranquility"]))
    with ctx.setup_item("warm"):
        st.warm = []
        for n in range(WARM_PASSES):
            t_wall = time.time()
            planted = await asyncio.to_thread(
                plant, st, ctx.seed, WARM_PASS + n,
                0.0 if n == 0 else st.last_pass_wall - 0.05)
            st.warm.append({**planted, **await one_pass(st)})
            st.last_pass_wall = t_wall
            if st.parity:
                await asyncio.to_thread(survey_sidecars, st)
    return st


async def one_more_pass(ctx, st, passes, watch, t0, seconds) -> bool:
    """Plant and scrub once more; → whether that was the window's last
    pass.  A traced run's profile holds the window's last pass, whole."""
    pass_no = len(passes) + 1
    t_wall, t_pass = time.time(), time.monotonic()
    built0, loaded0 = ctx.compiles.count, ctx.compiles.cache_hits
    traced = None
    if ctx.trace and (t_pass - t0 + 0.9 * (passes or st.warm)[-1]["seconds"]
                      >= seconds):
        traced = {"before": st.admin.metrics()}

        def stop():
            traced["after"] = st.admin.metrics()
            ctx.stop_trace()

        ctx.start_trace()
        stopper = asyncio.get_running_loop().call_later(TRACE_S, stop)
    with ctx.mark("plant"):
        planted = await asyncio.to_thread(
            plant, st, ctx.seed, pass_no, st.last_pass_wall - 0.05)
    t_planted = time.monotonic()
    with ctx.mark("scrub_pass"):
        done = await one_pass(st)
    st.last_pass_wall = t_wall
    loaded = ctx.compiles.cache_hits - loaded0
    passes.append({
        **planted, **done, **watch.take(),
        "plant_ms": round((t_planted - t_pass) * 1e3, 2),
        "at_s": round(t_pass - st.cluster_up, 1),
        "compiled": ctx.compiles.count - built0 - loaded,
        "loaded": loaded})
    if traced is not None:
        stopper.cancel()
        traced.setdefault("after", st.admin.metrics())
        st.traced = traced
    if ctx.trace:       # a traced window ends with its traced pass
        return traced is not None
    return time.monotonic() - t0 >= seconds


async def window(ctx, st, seconds: float) -> dict:
    if ctx.trace:
        seconds = min(seconds, TRACED_WINDOW_S)
    passes = []
    watch = Watch(ctx.tmp / "stalls.txt")
    metrics0 = st.admin.metrics()
    t0 = time.monotonic()
    try:
        while not await one_more_pass(ctx, st, passes, watch, t0, seconds):
            pass
    finally:
        t1 = time.monotonic()
        stacks = watch.stop()
    quarantined = (cl.metric_sum(st.admin.metrics(), "block_quarantine_total")
                   - cl.metric_sum(metrics0, "block_quarantine_total"))
    total = sum(p["bytes"] for p in passes)

    def each(key):
        return [p[key] for p in passes]

    notes = [
        f"passes in the window: {len(passes)}, seconds each "
        f"{[round(s, 3) for s in each('seconds')]}, window "
        f"{round(t1 - t0, 3)} s for --seconds {seconds}",
        f"each pass began, seconds after the cluster was up: "
        f"{each('at_s')}; planting took ms {each('plant_ms')}",
        f"programs compiled in each pass {each('compiled')}, loaded from "
        f"the persistent cache {each('loaded')}; event loop's longest "
        f"lag ms {each('loop_lag_ms')}, full collections ms {each('gc_ms')}",
        f"blocks per pass {each('blocks')}, sidecars removed per pass "
        f"{[len(r) for r in each('removed')]}, corruptions found per pass "
        f"{each('found')}, quarantined in the window {int(quarantined)}",
        f"samples: scrub_mib_s is {total} bytes over {round(t1 - t0, 3)} s",
    ]
    if stacks:
        notes.append(f"the event loop stood still for {Watch.STALL_S} s or "
                     f"more; every thread then: {stacks[:20000]!r}")
    return {
        "end_to_end": {"scrub_mib_s": arith.rate(total / cl.MIB, t1 - t0)},
        "attempted": sum(each("blocks")),
        "failed": 0,
        "window_s": t1 - t0,
        "bytes_verified": total,
        "bytes_traced": scrubbed_between(st.traced) if ctx.trace else None,
        "passes": passes,
        "quarantined": quarantined,
        "notes": notes,
    }


def scrubbed_between(traced: dict) -> float:
    """Bytes the scrub asked the pool for while the profiler ran: every
    scrubbed byte is a pool hit or a pool miss (`/metrics`)."""
    if "after" not in traced:
        return 0.0
    return sum(cl.metric_sum(traced["after"], fam)
               - cl.metric_sum(traced["before"], fam)
               for fam in ("pool_hit_bytes_total", "pool_miss_bytes_total"))


def expected_ids(plan, seed: int, block: int):
    """The reference's block ids of the store: BLAKE2s of every block of
    every seeded object."""
    from benchmarks.reference import block_id

    ids = set()
    for _key, idx, n in plan:
        body = cl.object_bytes(seed, idx, n)
        ids.update(block_id(body[o:o + block]) for o in range(0, n, block))
    return ids


def check_store(st, ids: set) -> int:
    """Block files on the node under test that are not what the
    reference says the store holds: wrong bytes, missing, or extra."""
    from benchmarks.reference import block_id

    files = cl.block_files(st.data_dir)
    bad = sum(1 for h, p in files if block_id(open(p, "rb").read()) != h)
    have = {h for h, _p in files}
    return bad + len(ids - have) + len(have - ids)


def check_sidecars(st, removed, reference, k: int, m: int):
    """Sidecars removed before a pass of the window, as that pass wrote
    them again, against the reference's parity of their member blocks.
    → (wrong or not rewritten, compared)."""
    by_hash = dict(cl.block_files(st.data_dir))
    wrong = 0
    for path in removed:
        if not os.path.exists(path):
            print(f"sidecar not written again: {path}", flush=True)
            wrong += 1
            continue
        man = read_sidecar(path)
        members = [bytes(h).hex() for h in man["hashes"]]
        if any(h not in by_hash for h in members):
            print(f"sidecar of a block the store lacks: {path}", flush=True)
            wrong += 1
            continue
        raws = [open(by_hash[h], "rb").read() for h in members]
        ref = reference.codeword_parity(raws, man["maxlen"], k, m)
        got = np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]])
        wrong += not np.array_equal(ref, got)
    return wrong, len(removed)


async def check(ctx, st, win: dict) -> dict:
    reference = ctx.cell.reference
    config = ctx.config
    planted = [v for p in win["passes"] for v in p["victims"]]
    # a healed block is back under its name with its own bytes; the
    # replicas' copies come through resync, so give them a moment
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if all(os.path.exists(p) for _h, p in st.victims):
            break
        await asyncio.sleep(0.05)
    ids = await asyncio.to_thread(expected_ids, st.plan, ctx.seed,
                                  config["block_size"])
    compared = {
        "blocks_wrong": {
            "value": await asyncio.to_thread(check_store, st, ids),
            "limit": 0},
        "plants_missed": {
            "value": abs(len(planted) - int(win["quarantined"]))
            + abs(len(planted) - sum(p["found"] for p in win["passes"])),
            "limit": 0},
    }
    if st.parity:
        removed = [p for ps in win["passes"] for p in ps["removed"]]
        wrong, n = await asyncio.to_thread(
            check_sidecars, st, removed, reference, config["rs_data"],
            config["rs_parity"])
        # nothing compared is as wrong as a wrong one
        compared["parity_wrong"] = {"value": wrong + (n == 0), "limit": 0}
        print(f"sidecars compared with the reference: {n}", flush=True)
    rng = np.random.default_rng([ctx.seed, 11])
    sample = [st.plan[int(i)] for i in rng.choice(
        len(st.plan) - 1, min(GET_SAMPLE, len(st.plan) - 1),
        replace=False)] + [st.plan[-1]]
    wrong = 0
    for key, idx, n in sample:
        status, _h, body = await st.s3.req("GET", f"/{BUCKET}/{key}")
        wrong += not (status == 200
                      and body == cl.object_bytes(ctx.seed, idx, n))
    compared["gets_wrong"] = {"value": wrong, "limit": 0}
    return compared


def control(cell):
    """The cell's control, for `ctx.after_cluster`: the reference's
    broken scrub where the codec's answer is produced, the feeder call
    the scrub worker makes for every batch."""
    def install(st):
        k, m = cell.config["rs_data"], cell.config["rs_parity"]
        feeder = st.cluster.garages[st.node].block_manager.feeder

        async def scrub_async(blocks, hashes, want_parity=True):
            return cell.reference.control_scrub(
                list(blocks), list(hashes), want_parity, k, m)

        feeder.scrub_async = scrub_async
    return install


async def shutdown(st) -> None:
    await st.session.close()
    await st.cluster.stop()

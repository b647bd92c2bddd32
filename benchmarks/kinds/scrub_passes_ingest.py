"""Traffic kind `scrub_passes_ingest`: the passes of `scrub_passes` over
a store that took writes since the last pass.  Upstream starts a full
scrub pass every 25 to 35 days, so every pass of a node that is in use
meets blocks the last pass never saw.

Mix parameters: those of `scrub_passes` (`sidecars_per_pass` is 0 here:
no sidecar is removed, the codewords that moved lack theirs), and
    objects_per_pass   new objects PUT before every pass, all in flight
                       together, through the S3 endpoint on node 0
    object_bytes       the size of each
The configuration states the same two under `ingest`; set-up refuses a
mix and a configuration that differ.

The window, until `--seconds` have passed and the pass in flight has
ended:
  1. plant `corrupt_per_pass` byte flips in block files drawn from
     SET-UP's listing (the store as loaded; `scrub_passes.plant`);
  2. PUT the pass's objects, keys `ingest/<pass>/<i>`, bodies the
     reference's `object_bytes(seed, index)` at indices no set-up
     object has; at each acknowledgement count the nodes that hold the
     block's file (`puts_unreplicated`); then wait, inside the window
     and with the wait noted, until the node under test holds them all;
  3. start a pass (`launch_repair scrub start`) and wait for its end.
No request is in flight while a pass runs.  `scrub_mib_s` keeps its
meaning: the bytes of block data on the node at the start of each pass
(the new blocks count from the pass after their PUT) over the seconds
of the window, planting, PUTs and waits included.

Set-up: load, settle, then warm passes WITH the ingest before each,
until one builds no program: the `hash` kind's programs, the write-time
encode's and a heal after a moved membership are geometries a static
warm pass never builds.

`check` holds the program to the configuration's guarantees through the
reference's model of the bucket (`Bucket`) and through the sidecars on
disk, each judged as the codeword it states.  Nothing here knows how
the program groups blocks into codewords: not their number, not their
order, not which pass's listing they follow.
"""

import asyncio
import hashlib
import os
import pathlib
import time

import numpy as np

from benchmarks import arith
from benchmarks import cluster as cl
from benchmarks import harness

base = harness.load_module(
    pathlib.Path(__file__).with_name("scrub_passes.py"),
    "bench_kind_scrub_passes_under_ingest")
control, shutdown = base.control, base.shutdown
BUCKET = base.BUCKET
# Warm passes, the ingest before each: at least the base kind's two (an
# empty pool, then a filled one), then until one builds or loads no
# program, and no more than this many.
WARM_PASSES_MOST = 6
REPLICA_WAIT_S = 30.0   # an acknowledged block reaches the third node
HEAL_WAIT_S = 30.0
SIDECAR_SAMPLE = 64     # sidecars compared with the reference, at most
INGEST_SAMPLE = 4       # ingested objects read back, beside first and last


def block_path(data_dir: str, h: str) -> str:
    return os.path.join(data_dir, h[:2], h[2:4], h)


def prepare(st, seed: int, pass_no: int):
    """The pass's objects: (key, index, body, its SHA-256 for the
    signature, the reference's ids of its blocks)."""
    n, size = st.mix["objects_per_pass"], st.mix["object_bytes"]
    block = st.config["block_size"]
    out = []
    for i in range(n):
        index = len(st.plan) + st.puts_prepared
        st.puts_prepared += 1
        body = st.reference.object_bytes(seed, index, size)
        out.append((f"ingest/{pass_no}/{i}", index, body,
                    hashlib.sha256(body).hexdigest(),
                    [st.reference.block_id(body[o:o + block])
                     for o in range(0, size, block)]))
    return out


async def ingest(st, seed: int, pass_no: int) -> dict:
    """PUT the pass's objects, all in flight together; at each
    acknowledgement, before anything else runs, count the nodes that
    hold the object's blocks; then wait until the node under test does."""
    t0 = time.monotonic()
    objects = await asyncio.to_thread(prepare, st, seed, pass_no)
    failed = unreplicated = 0

    async def put(key, index, body, sha256, ids):
        nonlocal failed, unreplicated
        status, _h, answer = await st.s3.req(
            "PUT", f"/{BUCKET}/{key}", body, payload_sha256=sha256)
        if status != 200:
            failed += 1
            harness.log(f"ingest PUT {key}: {status} {answer[:200]!r}")
            return
        holders = min(sum(os.path.exists(block_path(d, h))
                          for d in st.cluster.data_dirs) for h in ids)
        if holders < st.config["write_quorum"]:
            unreplicated += 1
            harness.log(f"ingest PUT {key} acknowledged with a block on "
                        f"{holders} node(s)")
        st.bucket.acknowledged(key, index, len(body))
        st.ingested.append((key, len(ids)))

    await asyncio.gather(*[put(*o) for o in objects])
    t_acked = time.monotonic()
    mine = [block_path(st.data_dir, h)
            for _k, _i, _b, _s, ids in objects for h in ids]
    deadline = t_acked + REPLICA_WAIT_S
    while not all(os.path.exists(p) for p in mine):
        if time.monotonic() > deadline:
            raise RuntimeError(f"node {st.node} does not hold the blocks "
                               f"of pass {pass_no}'s PUTs")
        await asyncio.sleep(base.POLL_S)
    return {"puts": len(objects), "puts_failed": failed,
            "puts_unreplicated": unreplicated,
            "ingest_ms": round((t_acked - t0) * 1e3, 2),
            "replica_wait_ms": round((time.monotonic() - t_acked) * 1e3, 2)}


# what a pass's line in the notes says of the pass and of the parity
# store, from the program's counters (`/metrics` once a pass, after its
# end); None where the program has no such family
PASS_COUNTERS = {
    "verified": ("scrub_verified_blocks_total", {}),
    "quarantined": ("block_quarantine_total", {}),
    "rows_lacking": ("scrub_parity_rows_total", {"fetch": "fetched"}),
    "purged": ("parity_purged_sidecars_total", {}),
    "written_scrub": ("parity_codewords_written_total", {"origin": "scrub"}),
    "written_write": ("parity_codewords_written_total", {"origin": "write"}),
    "written_heal": ("parity_codewords_written_total", {"origin": "heal"}),
    "programs": ("codec_compiles_total", {}),
}


def counters(st) -> dict:
    m = st.admin.metrics()
    families = {series.partition("{")[0] for series in m}
    return {name: cl.metric_sum(m, fam, **labels) if fam in families
            else None for name, (fam, labels) in PASS_COUNTERS.items()}


async def plant_ingest_scrub(ctx, st, pass_no: int) -> dict:
    """One round: plant, ingest, pass.  → the round's line."""
    t_pass = time.monotonic()
    with ctx.mark("plant"):
        # no sidecar is removed, so none has to be fresh since anything
        planted = await asyncio.to_thread(base.plant, st, ctx.seed, pass_no,
                                          0.0)
    t_planted = time.monotonic()
    with ctx.mark("ingest"):
        took = await ingest(st, ctx.seed, pass_no)
    # what the pass has to verify: every block acknowledged by now, but
    # for the planted ones a heal has not brought back yet
    planted["blocks"] += sum(n for _key, n in st.ingested)
    planted["bytes"] += len(st.ingested) * st.mix["object_bytes"]
    with ctx.mark("scrub_pass"):
        done = await base.one_pass(st)
    t_done = time.monotonic()
    now = counters(st)
    line = {**planted, **took, **done,
            "plant_ms": round((t_planted - t_pass) * 1e3, 2),
            "counters_ms": round((time.monotonic() - t_done) * 1e3, 2),
            **{name: None if v is None else int(v - st.counters[name])
               for name, v in now.items()}}
    st.counters = now
    return line


async def setup(ctx):
    mix, stated = ctx.mix, ctx.config["ingest"]
    if any(mix[key] != stated[key] for key in stated):
        raise RuntimeError(f"benchmarks: the mix's ingest {mix} is not the "
                           f"configuration's {stated}: no result")
    warm, base.WARM_PASSES = base.WARM_PASSES, 0    # the warm passes: below
    try:
        st = await base.setup(ctx)
    finally:
        base.WARM_PASSES = warm
    st.config, st.reference = ctx.config, ctx.cell.reference
    st.bucket = st.reference.Bucket(ctx.seed, ctx.config["block_size"],
                                    st.plan)
    st.ingested, st.puts_prepared = [], 0   # (key, its blocks), in PUT order
    st.endpoint = st.cluster.admins[0]
    st.counters = counters(st)
    with ctx.setup_item("warm"):
        for n in range(WARM_PASSES_MOST):
            st.warm.append(await plant_ingest_scrub(
                ctx, st, base.WARM_PASS + n))
            if n + 1 >= warm and not st.warm[-1]["programs"]:
                break
        harness.log(f"warm passes, the ingest before each: {len(st.warm)}, "
                    f"seconds each "
                    f"{[round(w['seconds'], 2) for w in st.warm]}")
    return st


async def one_more_pass(ctx, st, passes, watch, t0, seconds) -> bool:
    """`scrub_passes.one_more_pass` with the ingest between the planting
    and the pass; → whether that was the window's last pass."""
    pass_no = len(passes) + 1
    t_pass = time.monotonic()
    built0, loaded0 = ctx.compiles.count, ctx.compiles.cache_hits
    traced = None
    if ctx.trace and (t_pass - t0 + 0.9 * (passes or st.warm)[-1]["seconds"]
                      >= seconds):
        traced = {"before": st.admin.metrics()}
        ctx.start_trace()
    line = await plant_ingest_scrub(ctx, st, pass_no)
    if traced is not None:
        traced["after"] = st.admin.metrics()
        st.traced = traced
    loaded = ctx.compiles.cache_hits - loaded0
    passes.append({
        **line, **watch.take(),
        "at_s": round(t_pass - st.cluster_up, 1),
        "compiled": ctx.compiles.count - built0 - loaded,
        "loaded": loaded})
    if ctx.trace:       # a traced window ends with its traced pass
        return traced is not None
    return time.monotonic() - t0 >= seconds


async def window(ctx, st, seconds: float) -> dict:
    if ctx.trace:
        seconds = min(seconds, base.TRACED_WINDOW_S)
    passes = []
    watch = base.Watch(ctx.tmp / "stalls.txt")
    endpoint0 = st.endpoint.metrics()
    st.window_wall = time.time()
    t0 = time.monotonic()
    try:
        while not await one_more_pass(ctx, st, passes, watch, t0, seconds):
            pass
    finally:
        t1 = time.monotonic()
        stacks = watch.stop()
    endpoint1 = st.endpoint.metrics()
    total = sum(p["bytes"] for p in passes)

    def each(key):
        return [p[key] for p in passes]

    quarantined = sum(each("quarantined"))

    notes = [
        f"passes in the window: {len(passes)}, seconds each "
        f"{[round(s, 3) for s in each('seconds')]}, window "
        f"{round(t1 - t0, 3)} s for --seconds {seconds}",
        f"each pass began, seconds after the cluster was up: "
        f"{each('at_s')}; planting took ms {each('plant_ms')}, reading the "
        f"node's counters after the pass ms {each('counters_ms')}",
        f"the ingest before each pass: PUTs {each('puts')}, failed "
        f"{each('puts_failed')}, to the last acknowledgement ms "
        f"{each('ingest_ms')}, then until node {st.node} held the blocks "
        f"ms {each('replica_wait_ms')}",
        f"programs compiled in each pass {each('compiled')}, loaded from "
        f"the persistent cache {each('loaded')}; event loop's longest "
        f"lag ms {each('loop_lag_ms')}, full collections ms {each('gc_ms')}",
        f"blocks per pass {each('blocks')}, of them verified "
        f"{each('verified')}; corruptions found per pass {each('found')}, "
        f"quarantined in the window {int(quarantined)}",
        f"codewords that lacked a sidecar per pass {each('rows_lacking')}; "
        f"sidecars purged per pass {each('purged')}; sidecars written per "
        f"pass by the scrub {each('written_scrub')}, by the write-time "
        f"accumulator {each('written_write')}, after a heal "
        f"{each('written_heal')} (None: not counted by this program)",
        f"samples: scrub_mib_s is {total} bytes over {round(t1 - t0, 3)} s",
    ]
    if stacks:
        notes.append(f"the event loop stood still for {base.Watch.STALL_S} s "
                     f"or more; every thread then: {stacks[:20000]!r}")
    return {
        "end_to_end": {"scrub_mib_s": arith.rate(total / cl.MIB, t1 - t0)},
        "attempted": sum(each("blocks")) + sum(each("puts")),
        "failed": sum(each("puts_failed")),
        "window_s": t1 - t0,
        "bytes_verified": total,
        "bytes_traced": (base.scrubbed_between(st.traced) if ctx.trace
                         else None),
        "passes": passes,
        "quarantined": quarantined,
        # the node whose S3 endpoint took the PUTs, for the readers that
        # are asked for its counters (`counter_ratio_on`)
        "endpoint": {"before": {"metrics": endpoint0},
                     "after": {"metrics": endpoint1}},
        "notes": notes,
    }


def sidecars_on_disk(st):
    """[(path, member ids, mtime)] of the sidecars on the node under
    test."""
    return [(p, base.sidecar_members(p), os.path.getmtime(p))
            for p in base.sidecar_files(st.data_dir)]


def check_sidecars(st, sidecars, seed: int):
    """Each of a seeded sample of `sidecars` (as `sidecars_on_disk`
    gives them) as the codeword it states, whatever its members' number
    and order: members the store lacks, or a parity that is not the
    reference's RS of the members' bytes.  → (wrong, compared)."""
    reference, config = st.reference, st.config
    k, m = config["rs_data"], config["rs_parity"]
    by_hash = dict(cl.block_files(st.data_dir))
    rng = np.random.default_rng([seed, 13])
    picked = sorted(rng.choice(len(sidecars),
                               min(SIDECAR_SAMPLE, len(sidecars)),
                               replace=False))
    wrong = 0
    for i in picked:
        path, members, _mtime = sidecars[int(i)]
        man = base.read_sidecar(path)
        if not 0 < len(members) <= k or any(
                h not in by_hash for h in members):
            print(f"sidecar of a block the store lacks: {path}", flush=True)
            wrong += 1
            continue
        raws = [open(by_hash[h], "rb").read() for h in members]
        ref = reference.codeword_parity(raws, man["maxlen"], k, m)
        got = np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]])
        if not np.array_equal(ref, got):
            print(f"sidecar whose parity is not the reference's: {path} "
                  f"({len(members)} members)", flush=True)
            wrong += 1
    return wrong, len(picked)


def get_sample(st, seed: int):
    """The objects read back: `scrub_passes`'s sample of the store as
    loaded, the first ingested object, the last, and a few drawn from
    the seed between them."""
    rng = np.random.default_rng([seed, 11])
    keys = [st.plan[int(i)][0] for i in rng.choice(
        len(st.plan) - 1, min(base.GET_SAMPLE, len(st.plan) - 1),
        replace=False)] + [st.plan[-1][0]]
    new = [key for key, _h in st.ingested]
    if new:
        between = new[1:-1]
        drawn = np.random.default_rng([seed, 17]).choice(
            len(between), min(INGEST_SAMPLE, len(between)), replace=False)
        keys += [new[0]] + [between[int(i)] for i in sorted(drawn)]
        keys += new[-1:] if len(new) > 1 else []
    return keys


async def check(ctx, st, win: dict) -> dict:
    passes = win["passes"]
    planted = [v for p in passes for v in p["victims"]]
    # a healed block is back under its name with its own bytes; the
    # replicas' copies come through resync, so give them a moment
    deadline = time.monotonic() + HEAL_WAIT_S
    while time.monotonic() < deadline:
        if all(os.path.exists(p) for _h, p in st.victims):
            break
        await asyncio.sleep(0.05)
    ids = await asyncio.to_thread(st.bucket.block_ids)
    on_disk = await asyncio.to_thread(sidecars_on_disk, st)
    inside = [s for s in on_disk if s[2] >= st.window_wall]
    wrong, n = await asyncio.to_thread(check_sidecars, st, inside, ctx.seed)
    print(f"sidecars on disk {len(on_disk)}, written or refreshed in the "
          f"window {len(inside)}, compared with the reference: {n}",
          flush=True)
    # the last pass verified every block of the bucket (nothing was PUT
    # after it began): each has to be some stored codeword's member,
    # but for fewer than k of them
    protected = {h for _p, members, _mtime in on_disk for h in members}
    compared = {
        "blocks_wrong": {
            "value": await asyncio.to_thread(base.check_store, st, ids),
            "limit": 0},
        "plants_missed": {
            "value": abs(len(planted) - int(win["quarantined"]))
            + abs(len(planted) - sum(p["found"] for p in passes)),
            "limit": 0},
        "puts_unreplicated": {
            "value": sum(p["puts_unreplicated"] for p in passes),
            "limit": 0},
        "unverified": {
            "value": sum(max(0, p["blocks"] - p["verified"])
                         for p in passes),
            "limit": 0},
        # nothing compared is as wrong as a wrong one
        "parity_wrong": {"value": wrong + (n == 0), "limit": 0},
        "unprotected": {
            "value": max(0, len(ids - protected)
                         - (st.config["rs_data"] - 1)),
            "limit": 0},
    }
    wrong = 0
    for key in get_sample(st, ctx.seed):
        status, _h, body = await st.s3.req("GET", f"/{BUCKET}/{key}")
        wrong += not (status == 200 and body == st.bucket.reads_as(key))
    compared["gets_wrong"] = {"value": wrong, "limit": 0}
    return compared

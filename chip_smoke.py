#!/usr/bin/env python3
"""Does the served block path still start — and really compute — on the chip?

Default run (one chip): three in-process Garage nodes under the
production default `[codec]` (hybrid backend, RS(8,4), 1 MiB blocks,
feeder, transport, 256 MiB pool) plus `store_parity`, loaded through the
S3 API with SigV4, two planted corruptions, two operator-started scrub
passes per node.  The run fails on any wrong byte AND on any fallback
that would hide the chip (CPU routes, kernel demotions, transport
fallbacks, a device array that is not on a TPU).

`--chips 4` runs only the sharded path (`[codec] shard_mesh = 4`) and
what it is compared with (`shard_mesh = 1` over the same store, and the
numpy/hashlib reference).

One process; the only child is `make`.  Exits non-zero, and prints no
result line, when JAX finds no TPU.  All timings are smoke timings —
none is a benchmark number.  The last stdout line is the result object.
"""

import argparse
import asyncio
import collections
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20
RESULT_KEYS = ("platform", "kind", "count")


class Sizes:
    """The default run's load.  A rehearsal (CPU, tiny) passes smaller
    numbers by calling run_one_chip()/run_four_chips() itself; the
    command line has no switch for them."""

    block = MIB            # the published block size
    n_small = 512          # 1-block objects
    n_big = 4              # multi-block objects
    big_blocks = 16
    mesh_blocks = 256      # --chips 4: blocks in the one node's store
    parity_sample = 8      # codewords per node checked against numpy
    concurrency = 8


class Smoke:
    """Phase timing, compile accounting and the pass/fail ledger."""

    def __init__(self):
        self.checks = []       # (name, ok, detail)
        self.phases = collections.OrderedDict()
        self.compile_s = collections.Counter()
        self.cache_hits = 0

    def check(self, name, ok, detail=""):
        self.checks.append((name, bool(ok), detail))
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""), flush=True)
        return bool(ok)

    def phase(self, name):
        smoke = self

        class _P:
            def __enter__(self):
                print(f"== {name}", flush=True)
                self.t0 = time.monotonic()

            def __exit__(self, *exc):
                smoke.phases[name] = round(time.monotonic() - self.t0, 3)

        return _P()

    def listen_to_compiles(self):
        import jax.monitoring as mon

        def on_duration(event, secs, **_kw):
            if event.startswith("/jax/core/compile/"):
                self.compile_s[event.rsplit("/", 1)[1]] += secs

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @property
    def failed(self):
        return [n for n, ok, _ in self.checks if not ok]


def log(msg):
    print(msg, flush=True)


# --- seeded data ------------------------------------------------------------


def object_bytes(seed: int, index: int, n: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, index]).bytes(n)


def object_plan(sz: Sizes):
    """[(key, index, nbytes)] — every object's content is a function of
    (seed, index), so GET checks regenerate instead of holding 576 MiB."""
    plan = [(f"small/{i:04d}", i, sz.block) for i in range(sz.n_small)]
    plan += [(f"big/{i:02d}", sz.n_small + i, sz.big_blocks * sz.block)
             for i in range(sz.n_big)]
    return plan


# --- build ------------------------------------------------------------------


def build_native(smoke: Smoke) -> None:
    """Rebuild the CPU kernels HERE: the libraries are built with
    -march=native and must not travel between machines."""
    native = os.path.join(REPO, "garage_tpu", "native")
    r = subprocess.run(["make", "-B", "-C", native, "all"],
                       capture_output=True, text=True)
    if r.returncode != 0:
        sys.stderr.write(r.stdout + r.stderr)
        raise SystemExit("chip_smoke: native build failed")
    from garage_tpu.db import native_adapter
    from garage_tpu.ops import native as nat

    # the four libraries must load; inside them the kernels gate
    # themselves on this host's ISA (GFNI, AVX2/AVX-512) at run time
    libs = {
        "libgf256": nat.get_native_gf_matmul_blocks() is not None,
        "libblake2smb": nat.get_native_blake2s_multi() is not None,
        "libdirectio": nat.get_native_read_files() is not None,
    }
    try:
        native_adapter._load()
        libs["liblogdb"] = True
    except Exception as e:  # noqa: BLE001 — reported below
        libs["liblogdb"] = False
        log(f"  logdb load error: {e}")
    log("  ISA-gated kernels live: " + json.dumps({
        "gf256_ptrs": nat.get_native_gf_matmul_ptrs() is not None,
        "blake2s_rows": nat.get_native_blake2s_rows() is not None,
    }))
    smoke.check("native CPU libraries built here and loaded",
                all(libs.values()), json.dumps(libs))


# --- cluster ----------------------------------------------------------------


class Admin:
    """The operator's commands, through the handler the CLI reaches."""

    def __init__(self, garage):
        from garage_tpu.admin import AdminRpcHandler

        self.garage = garage
        self.rpc = AdminRpcHandler(garage, register_endpoint=False)

    async def cmd(self, cmd: str, **msg):
        out, _ = await self.rpc._handle(None, {"cmd": cmd, **msg}, None)
        if "err" in out:
            raise RuntimeError(f"admin {cmd}: {out['err']}")
        return out["ok"]

    def metrics(self) -> dict:
        """{series: value} of the text /metrics serves."""
        from garage_tpu.api.admin_server import metrics_body

        out = {}
        for line in metrics_body(self.garage).splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out


def metric_sum(metrics: dict, family: str, **labels) -> float:
    total = 0.0
    for series, v in metrics.items():
        name, _, rest = series.partition("{")
        if name == family and all(f'{k}="{val}"' in rest
                                  for k, val in labels.items()):
            total += v
    return total


async def gather_bounded(n: int, coros):
    sem = asyncio.Semaphore(n)

    async def one(c):
        async with sem:
            return await c

    return await asyncio.gather(*[one(c) for c in coros])


async def put_all(s3, bucket: str, plan, seed: int, conc: int):
    async def put(key, idx, n):
        st, _body, _h = await s3.req("PUT", f"/{bucket}/{key}",
                                     object_bytes(seed, idx, n))
        return key, st

    return [k for k, st in await gather_bounded(
        conc, [put(*p) for p in plan]) if st != 200]


async def get_all(s3, bucket: str, plan, seed: int, conc: int):
    async def get(key, idx, n):
        st, body, _h = await s3.req("GET", f"/{bucket}/{key}")
        return key, st == 200 and body == object_bytes(seed, idx, n)

    return [k for k, ok in await gather_bounded(
        conc, [get(*p) for p in plan]) if not ok]


async def wait_attached(admins, smoke: Smoke, timeout: float = 180.0):
    """The device codec attaches on a background thread; the smoke
    needs it up on every node before any traffic is judged."""
    deadline = time.monotonic() + timeout
    infos = []
    while time.monotonic() < deadline:
        infos = [await a.cmd("codec_info") for a in admins]
        if all(i.get("device_attached") and i.get("transport")
               for i in infos):
            break
        events = [e for a in admins for e in await a.cmd("codec_events")]
        if any(e["kind"] == "device_attach" and e["reason"] != "ok"
               for e in events):
            break
        await asyncio.sleep(0.5)
    for n, (a, info) in enumerate(zip(admins, infos)):
        ev = [e for e in await a.cmd("codec_events")
              if e["kind"] == "device_attach"]
        smoke.check(
            f"node {n}: device_attach ok, transport armed",
            bool(info.get("device_attached")) and bool(info.get("transport"))
            and [e["reason"] for e in ev] == ["ok"],
            f"backend={info.get('device_backend')} events={ev}")


# --- block files ------------------------------------------------------------


def block_files(data_dir: str):
    """[(hash hex, path)] of the plain block files under a data dir."""
    out = []
    for d1 in sorted(os.listdir(data_dir)):
        p1 = os.path.join(data_dir, d1)
        if len(d1) != 2 or not os.path.isdir(p1):
            continue
        for d2 in sorted(os.listdir(p1)):
            p2 = os.path.join(p1, d2)
            if len(d2) != 2 or not os.path.isdir(p2):
                continue
            for name in sorted(os.listdir(p2)):
                if len(name) == 64:
                    out.append((name, os.path.join(p2, name)))
    return out


def flip_byte(path: str, offset: int = 4096) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0x40]))


def check_digests(smoke: Smoke, label: str, files) -> int:
    bad = [h for h, p in files
           if hashlib.blake2s(open(p, "rb").read(),
                              digest_size=32).hexdigest() != h]
    smoke.check(f"{label}: {len(files)} block ids equal hashlib.blake2s "
                "of the stored bytes", not bad, f"mismatch={bad[:3]}")
    return sum(os.path.getsize(p) for _h, p in files)


def check_parity(smoke: Smoke, label: str, data_dir: str, files, k: int,
                 m: int, sample: int, seed: int) -> None:
    """Stored parity sidecars against the numpy GF(256) reference."""
    import msgpack
    import numpy as np

    from garage_tpu.ops import gf256

    by_hash = dict(files)
    pars = sorted(str(p) for p in
                  pathlib.Path(data_dir, "parity").rglob("*.par"))
    rng = np.random.default_rng([seed, 77])
    picks = [pars[i] for i in rng.permutation(len(pars))[:sample]]
    mat = gf256.rs_parity_matrix(k, m)
    checked = wrong = skipped = 0
    for path in picks:
        man = msgpack.unpackb(open(path, "rb").read(), raw=False)
        members = [bytes(h).hex() for h in man["hashes"]]
        if any(h not in by_hash for h in members):
            skipped += 1    # a member was compressed or moved: not ours
            continue
        shards = np.zeros((k, man["maxlen"]), dtype=np.uint8)
        for j, h in enumerate(members):
            raw = np.frombuffer(open(by_hash[h], "rb").read(), np.uint8)
            shards[j, :len(raw)] = raw
        ref = gf256.gf_matmul_blocks(mat, shards[None])[0]
        got = np.stack([np.frombuffer(p, np.uint8) for p in man["parity"]])
        checked += 1
        wrong += not np.array_equal(ref, got)
    smoke.check(f"{label}: stored parity equals the numpy GF(256) reference",
                checked > 0 and wrong == 0,
                f"sidecars={len(pars)} checked={checked} wrong={wrong} "
                f"skipped={skipped}")


# --- scrub ------------------------------------------------------------------


async def scrub_pass(admin: Admin, timeout: float = 900.0) -> dict:
    """One operator-started scrub pass on one node, to completion.
    Returns what the node's own surface says happened in it."""
    g = admin.garage
    before_m = admin.metrics()
    before_ev = (await admin.cmd("codec_events") or [{"seq": 0}])[-1]["seq"]
    t0_us = time.monotonic_ns() // 1000
    done0 = g.scrub_worker.state.time_last_complete
    t0 = time.monotonic()
    await admin.cmd("launch_repair", what="scrub", scrub_cmd="start")
    while (g.scrub_worker.state.time_last_complete == done0
           or g.scrub_worker.state.running):
        if time.monotonic() - t0 > timeout:
            raise RuntimeError("scrub pass did not finish")
        await asyncio.sleep(0.2)
    secs = time.monotonic() - t0
    after_m = admin.metrics()
    events = [e for e in await admin.cmd("codec_events")
              if e["seq"] > before_ev]
    # the pass's own event is recorded after its purge and checkpoint,
    # which the worker's state does not wait for
    for _ in range(150):
        tl = (await admin.cmd("device_timeline"))["traceEvents"]
        if any(e["name"] == "scrub pass" and e.get("ts", 0) >= t0_us
               for e in tl):
            break
        await asyncio.sleep(0.2)
    batches = []
    computed = []      # the variant of every `compute scrub` event
    lane = {"read files": [], "read slice": []}     # the I/O lane's events
    prefetch = {}
    codewords = {}     # the `scrub pass` event: what became of each
    for e in tl:
        if e.get("ts", 0) < t0_us or e.get("ph") != "X":
            continue
        if e["name"] == "compute scrub":
            computed.append(e["args"].get("variant"))
        elif e["name"] == "scrub pass":
            codewords = {k: e["args"].get(k) for k in (
                "rows", "settled", "rewritten", "formed", "dissolved",
                "rows_host")}
        elif e["name"] in lane:
            lane[e["name"]].append(e)
        elif e["name"] == "stage scrub":
            prefetch[e["tid"]] = bool(e["args"].get("prefetch"))
        elif e["name"] == "submit scrub":
            a = e["args"]
            batches.append({"lanes": a["shape"][0], "cols": a["shape"][1],
                            "variant": a["variant"],
                            "compiled": a["compiled"],
                            "prefetch": prefetch.get(e["tid"], False)})

    def delta(family, **labels):
        return (metric_sum(after_m, family, **labels)
                - metric_sum(before_m, family, **labels))

    return {
        "seconds": round(secs, 3),
        "tpu_bytes": delta("codec_bytes_total", side="tpu"),
        "cpu_bytes": delta("codec_bytes_total", side="cpu"),
        "pool_hit_bytes": delta("pool_hit_bytes_total"),
        "pool_miss_bytes": delta("pool_miss_bytes_total"),
        "hints_sent": delta("scrub_prefetch_hints_total", hint="sent"),
        "hints_skipped": delta("scrub_prefetch_hints_total", hint="skipped"),
        "quarantined": delta("block_quarantine_total"),
        "corruptions": g.scrub_worker.state.corruptions,
        "events": events,
        "batches": batches,
        "computed": computed,
        "lane": lane,
        "codewords": codewords,
    }


HIDING_EVENTS = ("gf_demote", "fused_demote", "fused_transient",
                 "transport_fallback", "transport_error", "transport_down")


def judge_on_device(smoke: Smoke, label: str, p: dict, scrubbed: int) -> None:
    """The pass ran on the device side and nothing hid a fallback."""
    cpu_routes = [e for e in p["events"] if e["kind"] == "feeder_route"
                  and e["reason"] == "cpu"]
    hiding = [e for e in p["events"] if e["kind"] in HIDING_EVENTS]
    smoke.check(f"{label}: no batch routed to the CPU", not cpu_routes,
                str(cpu_routes[:2]))
    smoke.check(f"{label}: no demotion, fallback or transport error",
                not hiding, str(hiding[:2]))
    smoke.check(f"{label}: codec_bytes_total{{side=tpu}} grew by the bytes "
                "scrubbed", p["tpu_bytes"] >= scrubbed,
                f"tpu +{int(p['tpu_bytes'])} cpu +{int(p['cpu_bytes'])} "
                f"scrubbed {scrubbed}")


def lane_faults(lane: dict) -> list:
    """What is wrong with a pass's account of its I/O lane, as the ring
    holds it: every `read files` event carries the account, has its
    slices inside it, each slice's stages sum to its wall, and the
    slices sum to the batch (all to the microsecond a term the ring
    rounds to)."""
    from garage_tpu.block.repair import SCRUB_IO_STAGES

    faults = []
    stages = SCRUB_IO_STAGES[1:]        # a slice's: all but `list`
    carried = [f"{s}_ms" for s in SCRUB_IO_STAGES] + [
        "cpu_ms", "slices_ms", "direct", "buffered"]
    for b in lane["read files"]:
        a = b["args"]
        if any(k not in a for k in carried):
            faults.append(f"no account on {a}")
            continue
        mine = [s["args"] for s in lane["read slice"]
                if b["ts"] <= s["ts"] and s["ts"] + s["dur"]
                <= b["ts"] + b["dur"] + 1]
        if len(mine) != a["slices"]:
            faults.append(f"{len(mine)} slices of {a['slices']} in the ring")
            continue
        for sa in mine:
            off = sum(sa[f"{s}_ms"] for s in stages) - sa["wall_ms"]
            if abs(off) > 0.004:
                faults.append(f"a slice's stages miss its wall by {off} ms")
        for key in carried[1:]:
            of_slices = sum(sa["wall_ms" if key == "slices_ms" else key]
                            for sa in mine)
            if abs(of_slices - a[key]) > 0.001 * (len(mine) + 1):
                faults.append(f"{key}: slices {of_slices}, batch {a[key]}")
    return faults


def judge_pass(smoke: Smoke, label: str, p: dict, scrubbed: int) -> None:
    judge_on_device(smoke, label, p, scrubbed)
    # the I/O lane accounts for itself (block/repair.py `_read_slice`)
    reads = {m: sum(b["args"].get(m, 0) for b in p["lane"]["read files"])
             for m in ("direct", "buffered")}
    faults = lane_faults(p["lane"])
    smoke.check(f"{label}: every `read files` event carries the lane's "
                "account and its slices' stages sum to their walls",
                bool(p["lane"]["read files"]) and not faults,
                f"reads {reads} in {len(p['lane']['read files'])} batches; "
                f"{faults[:3]}")
    # nothing wraps a node's disk here and the library was built above:
    # every slice is read inside native/directio.cpp, and where the
    # smoke's stores live every open takes O_DIRECT
    roads = collections.Counter(
        s["args"].get("road") for s in p["lane"]["read slice"])
    smoke.check(f"{label}: every `read slice` event says road native, and "
                "no read was buffered",
                bool(roads) and set(roads) == {"native"}
                and reads["buffered"] == 0,
                f"slices by road {dict(roads)}; reads {reads}")
    # on one chip a batch under 128 lanes (the pass's tail and its hint)
    # is padded on the device to a row the Pallas kernels tile
    # (TpuCodec.scrub_device_lanes): nothing is left to the XLA scan
    smoke.check(f"{label}: no `compute scrub` event has variant xla",
                bool(p["computed"]) and "xla" not in p["computed"]
                and all(b["variant"] == "pallas" for b in p["batches"]),
                f"computed {collections.Counter(p['computed'])} lanes "
                f"{sorted({b['lanes'] for b in p['batches']})}")


def print_batches(label: str, p: dict) -> None:
    for b in p["batches"]:
        log(f"  {label} batch: lanes={b['lanes']} cols={b['cols']} "
            f"variant={b['variant']} compiled={b['compiled']} "
            f"prefetch={b['prefetch']}")


# --- the one-chip run -------------------------------------------------------


async def run_one_chip(smoke: Smoke, sz: Sizes, seed: int, tmp: pathlib.Path,
                       platform: str = "tpu") -> None:
    import aiohttp
    import jax
    import numpy as np

    from garage_tpu.testing.local_cluster import S3, mk_cluster

    codec_cfg = {"store_parity": True}      # everything else: the default
    with smoke.phase("cluster"):
        garages, server, port, kid, secret = await mk_cluster(
            tmp, 3, "3", codec_cfg, db="sqlite", block_size=sz.block)
        admins = [Admin(g) for g in garages]
        await wait_attached(admins, smoke)
        info0 = await admins[0].cmd("codec_info")
        params = info0["params"]
        log("  codec: " + json.dumps({k: params[k] for k in (
            "rs_data", "rs_parity", "batch_blocks", "pool_mib",
            "transport_staging_slots", "hybrid_min_link_gibs")}))
        smoke.check("production default codec",
                    info0["backend"] == "HybridCodec"
                    and (params["rs_data"], params["rs_parity"]) == (8, 4)
                    and params["pool_mib"] == 256 and params["transport"])
    plan = object_plan(sz)
    total = sum(n for _k, _i, n in plan)
    async with aiohttp.ClientSession() as session:
        s3 = S3(session, port, kid, secret)
        with smoke.phase("load"):
            st, _body, _h = await s3.req("PUT", "/smoke")
            smoke.check("bucket created", st == 200, f"status {st}")
            failed = await put_all(s3, "smoke", plan, seed, sz.concurrency)
            smoke.check(f"PUT {len(plan)} objects ({total >> 20} MiB) "
                        "through SigV4", not failed, str(failed[:3]))
            wrong = await get_all(s3, "smoke", plan, seed, sz.concurrency)
            smoke.check("GET every object byte-identical to the seeded "
                        "source", not wrong, str(wrong[:3]))
            for n, a in enumerate(admins):
                b = (await a.cmd("codec_info"))["bytes"]
                log(f"  node {n} foreground codec bytes by side: "
                    f"cpu={b['cpu']} tpu={b['tpu']}")
        # every replica has every block once the write quorum's stragglers
        # land; scrub judges what is on disk, so wait for the full set
        want_blocks = sz.n_small + sz.n_big * sz.big_blocks
        data_dirs = [str(tmp / f"n{i}" / "data") for i in range(3)]
        for _ in range(300):
            stores = [block_files(d) for d in data_dirs]
            if all(len(s) >= want_blocks for s in stores):
                break
            await asyncio.sleep(0.2)
        smoke.check("every node holds every block",
                    all(len(s) == want_blocks for s in stores),
                    str([len(s) for s in stores]))

        with smoke.phase("corrupt"):
            rng = np.random.default_rng([seed, 99])
            victims = [stores[1][i] for i in
                       rng.choice(len(stores[1]), 2, replace=False)]
            for h, path in victims:
                flip_byte(path)
                log(f"  flipped one byte of node 1 block {h[:16]}")

        passes = {}
        for pno in (1, 2):
            with smoke.phase(f"scrub pass {pno}"):
                for n, a in enumerate(admins):
                    # the operator's throttle (`worker set
                    # scrub-tranquility 0`): bounds the smoke's time,
                    # changes no route and no gate
                    await a.cmd("worker_set_var", var="scrub-tranquility",
                                value="0")
                    # one node at a time: three nodes share this one
                    # chip, and each may hold `transport_staging_slots`
                    # ~3 GiB submissions in flight
                    passes[pno, n] = p = await scrub_pass(a)
                    log(f"  pass {pno} node {n}: {p['seconds']} s, "
                        f"{len(p['batches'])} device batches, tpu "
                        f"+{int(p['tpu_bytes'])} B, cpu "
                        f"+{int(p['cpu_bytes'])} B, pool hit "
                        f"+{int(p['pool_hit_bytes'])} B, quarantined "
                        f"+{int(p['quarantined'])}")
                    print_batches(f"pass {pno} node {n}", p)

        with smoke.phase("correct"):
            p1 = passes[1, 1]
            smoke.check("both corruptions quarantined by node 1's first "
                        "pass", p1["quarantined"] == 2
                        and p1["corruptions"] == 2,
                        f"quarantined={p1['quarantined']} "
                        f"found={p1['corruptions']}")
            heals = (await admins[1].cmd("codec_info"))["heals"]
            healed = all(
                os.path.exists(p) and hashlib.blake2s(
                    open(p, "rb").read(), digest_size=32).hexdigest() == h
                for h, p in victims)
            smoke.check("both corruptions healed", healed
                        and sum(heals.values()) >= 2, f"heals={heals}")
            smoke.check("the judged pass found nothing more",
                        all(passes[2, n]["corruptions"] == 0
                            and passes[2, n]["quarantined"] == 0
                            for n in range(3)))
            wrong = await get_all(s3, "smoke", plan, seed, sz.concurrency)
            smoke.check("all GETs still 200 and byte-identical", not wrong,
                        str(wrong[:3]))
            scrubbed = []
            for n, d in enumerate(data_dirs):
                files = block_files(d)
                scrubbed.append(check_digests(smoke, f"node {n}", files))
                check_parity(smoke, f"node {n}", d, files,
                             params["rs_data"], params["rs_parity"],
                             sz.parity_sample, seed)

        with smoke.phase("a write between two passes"):
            # a codeword keeps its members (block/parity.py): node 1's
            # pass after one more PUT finds every codeword of the last
            # pass settled, forms at most one from the blocks that were
            # free and writes no sidecar again; its batches, their lanes
            # reordered on the host, still take the Pallas road
            extra = [("extra/0000", len(plan), sz.block)]
            failed = await put_all(s3, "smoke", extra, seed, 1)
            smoke.check("one more object PUT after the judged pass",
                        not failed, str(failed))
            for _ in range(300):
                if len(block_files(data_dirs[1])) > want_blocks:
                    break
                await asyncio.sleep(0.2)
            before = passes[2, 1]["codewords"]
            p3 = await scrub_pass(admins[1])
            cw = p3["codewords"]
            log(f"  pass 3 node 1: {p3['seconds']} s, codewords {cw} "
                f"(pass 2: {before})")
            print_batches("pass 3 node 1", p3)
            smoke.check("pass 3 node 1: formed <= 1, rewritten 0, every "
                        "codeword of pass 2 settled",
                        cw.get("formed") is not None and cw["formed"] <= 1
                        and cw["rewritten"] == 0 and cw["dissolved"] == 0
                        and cw["settled"] == before.get("rows", 0) > 0,
                        f"{cw} after {before}")
            smoke.check("pass 3 node 1: no `compute scrub` event has "
                        "variant xla",
                        bool(p3["computed"]) and "xla" not in p3["computed"],
                        str(collections.Counter(p3["computed"])))

        with smoke.phase("the chip did it"):
            for n, a in enumerate(admins):
                judge_pass(smoke, f"pass 2 node {n}", passes[2, n],
                           scrubbed[n])
                info = await a.cmd("codec_info")
                tr = info["transport"]
                smoke.check(f"node {n}: transport alive, dispatches > 0, "
                            "fallbacks == 0",
                            tr["alive"] and tr["dispatches"] > 0
                            and tr["fallbacks"] == 0,
                            f"dispatches={tr['dispatches']} "
                            f"fallbacks={tr['fallbacks']}")
                allev = await a.cmd("codec_events")
                bad = [e for e in allev if e["kind"] in HIDING_EVENTS
                       or (e["kind"] == "device_attach"
                           and e["reason"] != "ok")]
                smoke.check(f"node {n}: no demotion, fallback or failed "
                            "attach in the whole run", not bad,
                            str(bad[:2]))
                probes = [e for e in allev if e["kind"] == "transport_probe"]
                routes = [(e["reason"], e.get("prev")) for e in allev
                          if e["kind"] == "feeder_route"]
                floor = params["hybrid_min_link_gibs"]
                for e in probes:
                    log(f"  node {n} link probe: {e['gibs']} GiB/s over "
                        f"{e['stage_copy_bytes'] >> 20} MiB, dominant "
                        f"{e['dominant_stage']}, stages {e['stages']} — "
                        f"gate {'open' if e['gibs'] >= floor else 'SHUT'} "
                        f"(floor {floor} GiB/s)")
                log(f"  node {n} feeder routes: {routes}")
                smoke.check(f"node {n}: the link probe opened the gate",
                            bool(probes) and all(e["gibs"] >= floor
                                                 for e in probes))
                # a device array through the transport's own device API
                codec = garages[n].block_manager.codec
                h = codec.transport.device.probe_submit(
                    np.arange(1 << 16, dtype=np.uint8))
                plats = sorted({d.platform for d in h.devices()})
                smoke.check(f"node {n}: a transport device array is on "
                            f"a {platform} device", plats == [platform],
                            f"{sorted(map(str, h.devices()))}")
            # a batch the worker was already waiting for is not hinted
            # to the pool (block/repair.py `_read_ahead`), and a hinted
            # one hits only if its prefetch was adopted before the
            # worker's own submit: the worker is at its next batch within
            # milliseconds since a settled codeword costs it nothing, and
            # one node in three lost the tail's race on the chip.  So
            # the pool is judged over the nodes' passes, not in each
            judged = [passes[2, n] for n in range(len(admins))]
            smoke.check("pass 2: pool_hit_bytes_total grew on some node, "
                        "or no batch was hinted on any",
                        any(p["pool_hit_bytes"] > 0 for p in judged)
                        or not any(p["hints_sent"] for p in judged),
                        str([(int(p["pool_hit_bytes"]), int(p["hints_sent"]),
                              int(p["hints_skipped"])) for p in judged])
                        + " (hit bytes, hints sent, skipped) a node")
            stats = jax.devices()[0].memory_stats() or {}
            log(f"  device peak_bytes_in_use: "
                f"{stats.get('peak_bytes_in_use')} of "
                f"{stats.get('bytes_limit')}")

    with smoke.phase("shutdown"):
        await server.stop()
        for g in garages:
            await g.shutdown()


# --- the four-chip run ------------------------------------------------------


async def run_four_chips(smoke: Smoke, sz: Sizes, seed: int,
                         tmp: pathlib.Path) -> None:
    """`[codec] shard_mesh = 4` through the ScrubWorker road, against
    `shard_mesh = 1` over an identical store and the references."""
    import aiohttp
    import jax
    import numpy as np

    from garage_tpu.testing.local_cluster import S3, mk_cluster

    plan = [(f"mesh/{i:04d}", i, sz.block) for i in range(sz.mesh_blocks)]
    results = {}
    for mesh_n in (4, 1):
        label = f"shard_mesh={mesh_n}"
        root = tmp / f"mesh{mesh_n}"
        with smoke.phase(f"{label}: cluster + load"):
            garages, server, port, kid, secret = await mk_cluster(
                root, 1, "none",
                {"store_parity": True, "shard_mesh": mesh_n},
                db="sqlite", block_size=sz.block)
            admin = Admin(garages[0])
            await wait_attached([admin], smoke)
            codec = garages[0].block_manager.codec
            mesh = codec.tpu.mesh
            smoke.check(f"{label}: mesh size",
                        (mesh.size if mesh is not None else 1) == mesh_n)
            async with aiohttp.ClientSession() as session:
                s3 = S3(session, port, kid, secret)
                st, _body, _h = await s3.req("PUT", "/smoke")
                failed = await put_all(s3, "smoke", plan, seed,
                                       sz.concurrency)
                smoke.check(f"{label}: PUT {len(plan)} objects",
                            st == 200 and not failed, str(failed[:3]))
                wrong = await get_all(s3, "smoke", plan, seed,
                                      sz.concurrency)
                smoke.check(f"{label}: GETs byte-identical", not wrong)
        data_dir = str(root / "n0" / "data")
        files = block_files(data_dir)
        victim = files[len(files) // 2]
        flip_byte(victim[1])
        with smoke.phase(f"{label}: scrub"):
            await admin.cmd("worker_set_var", var="scrub-tranquility",
                            value="0")
            p1 = await scrub_pass(admin)
            p2 = await scrub_pass(admin)
            for pno, p in ((1, p1), (2, p2)):
                log(f"  {label} pass {pno}: {p['seconds']} s, tpu "
                    f"+{int(p['tpu_bytes'])} B, cpu "
                    f"+{int(p['cpu_bytes'])} B, quarantined "
                    f"+{int(p['quarantined'])}")
                print_batches(f"{label} pass {pno}", p)
        with smoke.phase(f"{label}: correct"):
            smoke.check(f"{label}: the planted corruption was quarantined "
                        "and healed", p1["quarantined"] == 1
                        and hashlib.blake2s(open(victim[1], "rb").read(),
                                            digest_size=32).hexdigest()
                        == victim[0])
            files = block_files(data_dir)
            scrubbed = check_digests(smoke, label, files)
            info = await admin.cmd("codec_info")
            check_parity(smoke, label, data_dir, files,
                         info["params"]["rs_data"],
                         info["params"]["rs_parity"], sz.parity_sample, seed)
            judge_on_device(smoke, f"{label} pass 2", p2, scrubbed)
            hiding = [e for e in await admin.cmd("codec_events")
                      if e["kind"] in HIDING_EVENTS]
            smoke.check(f"{label}: no fallback in the whole run",
                        not hiding and info["transport"]["fallbacks"] == 0,
                        str(hiding[:2]))
            variants = sorted({b["variant"] for b in p2["batches"]})
            log(f"  {label} pass 2 variants: {variants}")
            # a sharded device output, through the codec's own submit
            n = 64
            arr = np.frombuffer(object_bytes(seed, 10**6, n * 4096),
                                np.uint8).reshape(n, 4096)
            lengths = np.full((n,), 4096, np.int32)
            out = codec.tpu.hash_submit(arr, lengths)
            devset = out.sharding.device_set
            smoke.check(f"{label}: a device output spans {mesh_n} "
                        "device(s)", len(devset) == mesh_n,
                        str(sorted(map(str, devset))))
            ref = [hashlib.blake2s(arr[i].tobytes(),
                                   digest_size=32).digest()
                   for i in range(n)]
            smoke.check(f"{label}: sharded digests equal hashlib",
                        [bytes(h) for h in codec.tpu.hash_collect(out, n)]
                        == ref)
            used = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.devices()]
            log(f"  {label}: peak_bytes_in_use per device: {used}")
            results[mesh_n] = {
                p.name: hashlib.sha256(p.read_bytes()).hexdigest()
                for p in pathlib.Path(data_dir, "parity").rglob("*.par")}
            if mesh_n == 4:
                smoke.check("all four devices report bytes in use",
                            len(used) == 4 and all(u > 0 for u in used),
                            str(used))
        await server.stop()
        await garages[0].shutdown()
    # scrub-time codewords (hash-ordered members) are the same in both
    # stores; write-time ones group by arrival and need not be
    common = sorted(set(results[4]) & set(results[1]))
    smoke.check("shard_mesh=4 and shard_mesh=1 stored bit-identical "
                "scrub codewords",
                len(common) >= sz.mesh_blocks // 16
                and all(results[4][n] == results[1][n] for n in common),
                f"{len(common)} common of {len(results[4])} / "
                f"{len(results[1])}")


# --- entry ------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the shard_mesh=4 path and its comparison")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = dict(zip(RESULT_KEYS, (dev.platform, dev.device_kind,
                                    len(jax.devices()))))
    if dev.platform != "tpu":
        sys.stderr.write(f"chip_smoke: no TPU here (JAX found {device})\n")
        return 2
    if device["count"] < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} but {device}\n")
        return 2
    log(f"device: {json.dumps(device)}")

    smoke = Smoke()
    smoke.listen_to_compiles()
    t0 = time.monotonic()
    with smoke.phase("build"):
        build_native(smoke)
    from garage_tpu.ops.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="garage_tpu_smoke_"))
    try:
        run = run_four_chips if args.chips == 4 else run_one_chip
        asyncio.run(run(smoke, Sizes(), args.seed, tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    log("smoke timings (seconds, none is a benchmark number): "
        + json.dumps(smoke.phases))
    log(f"compile seconds: {json.dumps({k: round(v, 3) for k, v in smoke.compile_s.items()})}; "
        f"persistent-cache hits: {smoke.cache_hits}; cache dir: {cache_dir}")
    log(f"total {round(time.monotonic() - t0, 1)} s; "
        f"{len(smoke.checks) - len(smoke.failed)} of {len(smoke.checks)} "
        "checks passed")
    if smoke.failed:
        for name in smoke.failed:
            log(f"FAILED: {name}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

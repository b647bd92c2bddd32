"""The harness: finds a cell's files by name, runs set-up, window and
comparison through the cell's traffic kind, reads the per-layer metrics
through their readers, and makes the result line.

Everything that belongs to one configuration, one traffic mix, one
traffic kind or one per-layer metric is a file of its own under this
directory, found by the name `BENCHMARK.json` gives (see README.md):

    configs/<config>.json          the deployment as it is run
    configs/<config>.reference.py  its plain reference and its guarantees
    traffic/<traffic>.json         a mix: parameters of one kind
    kinds/<kind>.py                the general generator of a kind
    metrics/<metric>.json          which reader, with which parameters
    readers/<reader>.py            from counters, spans or trace to a number
"""

import asyncio
import collections
import contextlib
import importlib.util
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """A module by file path: names of cells, kinds and readers are file
    names, not import paths, so a new one needs no edit anywhere."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads` with the files it names."""

    def __init__(self, name: str, root: pathlib.Path = HERE,
                 manifest_path: pathlib.Path = None):
        self.root = pathlib.Path(root)
        self.manifest = load_json(manifest_path
                                  or self.root.parent / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        conf = next(c for c in self.manifest["configs"]
                    if c["name"] == self.entry["config"])
        self.config = load_json(self.root.parent / conf["file"])
        self.mix = load_json(
            self.root / "traffic" / f"{self.entry['traffic']}.json")
        self.kind = load_module(
            self.root / "kinds" / f"{self.mix['kind']}.py",
            f"bench_kind_{self.mix['kind']}")
        self.reference = load_module(
            self.root.parent / conf["file"].replace(".json", ".reference.py"),
            f"bench_reference_{conf['name']}".replace("-", "_"))

    def _metrics(self, group: str):
        out = []
        for m in self.manifest[group]:
            cells = m.get("workloads")
            if cells is not None and self.name not in cells:
                continue
            out.append(m)
        return out

    def end_to_end(self):
        """This cell's end-to-end metrics: those that list it, or list
        no cells at all (`setup_s`)."""
        return self._metrics("end_to_end")

    def per_layer(self):
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self._metrics("per_layer")
                if m["moves"] in reported]

    def read_per_layer(self, window: dict) -> dict:
        """Each per-layer metric through its reader.  A reader that finds
        nothing to read returns None and the metric is left out."""
        out = {}
        for m in self.per_layer():
            spec = load_json(self.root / "metrics" / f"{m['name']}.json")
            reader = load_module(
                self.root / "readers" / f"{spec['reader']}.py",
                f"bench_reader_{spec['reader']}")
            got = reader.read(window, **spec.get("params", {}))
            if got is not None:
                out[m["name"]] = {"value": float(got["value"]),
                                  "unit": m["unit"]}
                log(f"samples: {m['name']} over {got['samples']}")
        return out


class Ctx:
    """What a traffic kind is handed: the cell, the seed, a scratch
    directory, and the set-up ledger."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 tmp: pathlib.Path, t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.tmp, self.t_start = trace, tmp, t_start
        self.config, self.mix = cell.config, cell.mix
        self.setup_items = collections.OrderedDict()
        self.compiles = Compiles()
        # a control or a fault test puts something in the program's
        # place here, once the cluster is up; a benchmark run never does
        self.after_cluster = None
        self._tracing = False

    @contextlib.contextmanager
    def setup_item(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.setup_items[name] = round(
                self.setup_items.get(name, 0.0) + time.monotonic() - t0, 3)

    def start_trace(self) -> None:
        """The profiler, which a traced run's kind starts for the last
        part of its window (the harness stops it when the window ends):
        a scrub's unrolled hash is some 350,000 device ops a second, and
        a trace takes longer to read back than to make."""
        if not self.trace:
            return
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.tmp / "trace"),
                                 profiler_options=opts)
        self._tracing = True
        self._window_mark = jax.profiler.TraceAnnotation("bench:window")
        self._window_mark.__enter__()

    def stop_trace(self) -> None:
        if not self._tracing:
            return
        import jax.profiler

        self._tracing = False
        self._window_mark.__exit__(None, None, None)
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        log(f"profiler stopped and its trace written in "
            f"{round(time.monotonic() - t0, 1)} s")

    def mark(self, name: str):
        """A span of the benchmark's own in the profiler's trace, so an
        idle gap of the device can be put to what the host was doing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(f"bench:{name}")


class Compiles:
    """Compilations and persistent-cache hits, by JAX's own events."""

    def __init__(self):
        self.count = 0          # programs built or loaded from the cache
        self.seconds = 0.0
        self.cache_hits = 0     # of them, loaded from the persistent cache

    def listen(self) -> None:
        import jax.monitoring as mon

        def on_duration(event, secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)


async def snapshot(cluster, node: int) -> dict:
    """The counters of one node, as its operator surface gives them."""
    admin = cluster.admins[node]
    return {"metrics": admin.metrics(),
            "codec_info": await admin.cmd("codec_info"),
            "mono_us": time.monotonic_ns() // 1000}


def device_record(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


async def run_cell(ctx: Ctx) -> dict:
    """Set-up, window, per-layer readings, comparison.  Returns the
    result object; the caller prints it."""
    cell, kind = ctx.cell, ctx.cell.kind
    state = await kind.setup(ctx)
    try:
        node = state.node
        setup_s = time.monotonic() - ctx.t_start
        log("setup_s itemised: " + json.dumps(
            {**ctx.setup_items, "total": round(setup_s, 3)}))
        log(f"compiles in set-up: {ctx.compiles.count} "
            f"({round(ctx.compiles.seconds, 1)} s), persistent-cache hits "
            f"{ctx.compiles.cache_hits}")
        if ctx.trace:
            from . import annotate

            log(f"host functions annotated for the trace: "
                f"{annotate.install()}")
        before = await snapshot(state.cluster, node)
        compiles0 = (ctx.compiles.count, ctx.compiles.cache_hits)
        try:
            win = await kind.window(ctx, state, ctx.seconds)
        finally:
            ctx.stop_trace()
        after = await snapshot(state.cluster, node)
        device = device_record(cell.chips)
        built = ctx.compiles.count - compiles0[0]
        loaded = ctx.compiles.cache_hits - compiles0[1]
        log(f"compilations inside the window: {built - loaded} "
            f"(and {loaded} programs loaded from the persistent cache)")
        log(f"device peak bytes in use: {device['memory_peak_bytes']}")
        sides = {side: after["codec_info"]["bytes"][side]
                 - before["codec_info"]["bytes"][side]
                 for side in ("cpu", "tpu")}
        log(f"node {node} codec bytes by side in the window: {sides}")
        timeline = (await state.cluster.admins[node].cmd(
            "device_timeline"))["traceEvents"]
        win.update(before=before, after=after, node=node, timeline=[
            e for e in timeline if e.get("ph") == "X"
            and before["mono_us"] <= e.get("ts", 0) <= after["mono_us"]])
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        breakdown = None
        if ctx.trace:
            from . import trace_reduce

            t_read = time.monotonic()
            planes = trace_reduce.load_xplane(str(ctx.tmp / "trace"))
            log(f"trace read back in {round(time.monotonic() - t_read, 1)} s")
            log("trace planes and lines: " + json.dumps(
                {n: {ln: len(evs) for ln, evs in lines}
                 for n, lines in planes if not n.startswith("/host:")}))
            red = trace_reduce.reduce_trace(planes, cell.chips)
            log("device seconds by op (top 40): " + json.dumps(sorted(
                red["op_seconds"].items(), key=lambda kv: -kv[1])[:40]))
            if red["buffer_full"]:
                log("the device's trace buffer filled: busy_s and window_s "
                    f"speak for its first {round(red['window_s'], 3)} s only")
            win["trace"] = red
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            metrics = cell.read_per_layer(win)
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end()}
            for name, value in win["end_to_end"].items():
                metrics[name] = {"value": float(value), "unit": units[name]}
            missing = set(units) - set(metrics)
            if missing:
                raise RuntimeError(f"cell reports no {sorted(missing)}")
        for line in win.get("notes", []):
            log(line)
        t_check = time.monotonic()
        compared = await kind.check(ctx, state, win)
        log(f"comparison after the window: "
            f"{round(time.monotonic() - t_check, 2)} s")
    finally:
        await kind.shutdown(state)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "attempted": int(win["attempted"]),
        "failed": int(win["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def print_result(result: dict) -> None:
    """Each number compared beside its limit as the last lines of
    standard error, and the result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        sys.stderr.write(f"compared {name}: {c['value']} (limit "
                         f"{c['limit']})\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run_blocking(ctx: Ctx) -> dict:
    return asyncio.run(run_cell(ctx))

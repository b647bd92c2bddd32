"""The scrub road traces itself (ISSUE 26): `Timeline.span` on the one
clock, the scrub worker's span tree and its exact-sum account of a
pass, the transport's two stamped sections inside `adopt` and
`collect`, compiles put to the span they ran under, and the profiler's
clock tied to the ring's by `gt:clock`."""

import glob
import hashlib
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from garage_tpu.ops import compile_listener
from garage_tpu.ops.codec import CodecParams
from garage_tpu.ops.cpu_codec import CpuCodec
from garage_tpu.ops.device_pool import DevicePool
from garage_tpu.ops.transport import DeviceTransport, TransportItem
from garage_tpu.utils.data import Hash, blake2s_sum
from garage_tpu.utils.metrics import MetricsRegistry
from garage_tpu.utils.timeline import Timeline, clock_pair, innermost_span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- Timeline.span -----------------------------------------------------------


def test_span_records_what_event_would():
    tl = Timeline()
    with tl.span("compose", "slot0", miss_rows=3) as sp:
        sp.args["resident_rows"] = 5
    tl.event("compose", "slot0", sp.t0, sp.t1, miss_rows=3, resident_rows=5)
    by_span, by_event = tl.snapshot()
    assert by_span == by_event
    assert by_span["ph"] == "X" and by_span["cat"] == "transport"
    assert by_span["ts"] == sp.t0 // 1000
    assert by_span["dur"] == (sp.t1 - sp.t0) // 1000
    # a per-block section stays out of the ring
    with tl.span("put codeword", "scrub", record=False):
        assert innermost_span() == ("put codeword", tl)
    assert len(tl.snapshot()) == 2


def test_span_stack_is_per_thread_and_nests():
    tl = Timeline()
    seen = {}
    inside = threading.Event()
    release = threading.Event()

    def other():
        seen["before"] = innermost_span()
        with tl.span("pool adopt", "slot1"):
            seen["own"] = innermost_span()[0]
            inside.set()
            assert release.wait(10)
        seen["after"] = innermost_span()

    t = threading.Thread(target=other)
    with tl.span("submit scrub", "slot0"):
        t.start()
        assert inside.wait(10)
        with tl.span("compose", "slot0"):
            assert innermost_span()[0] == "compose"
        assert innermost_span()[0] == "submit scrub"
        release.set()
        t.join(10)
    assert not t.is_alive()
    assert innermost_span() is None
    assert seen == {"before": None, "own": "pool adopt", "after": None}


def test_span_enters_the_hook_as_gt_name_and_clock_mark_carries_its_stamp():
    calls = []

    class Mark:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            calls.append(("enter", self.name, self.kw, time.monotonic_ns()))

        def __exit__(self, *exc):
            calls.append(("exit", self.name, self.kw, time.monotonic_ns()))

    tl = Timeline()
    with tl.span("compose", "slot0"):
        pass                            # no hook installed: nothing entered
    assert calls == []
    tl.annotate = Mark
    with tl.span("compose", "slot0") as sp:
        pass
    (e0, n0, _k0, t_in), (e1, n1, _k1, t_out) = calls
    assert (e0, n0, e1, n1) == ("enter", "gt:compose", "exit", "gt:compose")
    assert t_in <= sp.t0 <= sp.t1 <= t_out   # the hook encloses the stamps
    del calls[:]
    t = time.monotonic_ns()
    tl.mark_clock(t)
    assert [(c[0], c[1], c[2]) for c in calls] == [
        ("enter", "gt:clock", {"mono_ns": t}),
        ("exit", "gt:clock", {"mono_ns": t})]
    other = tl.chrome_trace()["otherData"]
    pair = clock_pair()
    assert abs((other["time_ns"] - other["monotonic_ns"])
               - (pair["time_ns"] - pair["monotonic_ns"])) < 50_000_000


def test_timeline_module_costs_no_jax_import():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import garage_tpu.utils.timeline, "
         "garage_tpu.block.repair; print('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# --- the scrub worker's span tree and its account of a pass ------------------


async def _one_pass(worker):
    worker.send_command("start")
    while (await worker.work()).name in ("BUSY", "THROTTLED"):
        pass


@pytest.mark.asyncio
async def test_scrub_pass_span_tree_and_exact_sum_account(tmp_path):
    from garage_tpu.block import DataBlock
    from garage_tpu.block.parity import ParityStore
    from garage_tpu.block.repair import SCRUB_SEGMENTS, ScrubWorker
    from garage_tpu.db import open_db
    from tests.test_block import make_block_cluster
    from tests.test_table import shutdown

    systems, managers = await make_block_cluster(tmp_path, n=1, mode="1")
    m = managers[0]
    m.blocks_reconstructed = 0
    m.parity_store = ParityStore(m, open_db("memory"), m.codec)
    blocks = {}
    for i in range(16):
        d = (b"compressible " * 900 + os.urandom(50) if i == 3
             else os.urandom(9000 + 137 * i))
        blocks[bytes(blake2s_sum(d))] = d
        await m.write_block(blake2s_sum(d), DataBlock.from_buffer(d, 3))
    tl = m.codec.obs.timeline

    def events():
        return [e for e in tl.snapshot() if e["cat"] == "scrub"]

    w = ScrubWorker(m)
    seg = lambda s: w.m_segments.get(segment=s)      # noqa: E731
    await _one_pass(w)
    first = events()
    names = {e["name"] for e in first}
    assert {"scrub pass", "read wait", "read files",
            "codec wait", "parity write", "purge stale"} <= names
    # the one compressed block is inflated by the lane's thread that
    # read it: in `read files`, and in no segment of the worker's
    assert "decompress" not in names
    assert sum(e["args"]["inflated"] for e in first
               if e["name"] == "read files") == 1
    (root,) = [e for e in first if e["name"] == "scrub pass"]
    assert root["args"]["blocks"] == 16 and root["args"]["batches"] >= 1
    assert root["args"]["bytes"] == sum(map(len, blocks.values()))
    assert root["args"]["corruptions"] == 0
    # the tree: everything on the `scrub` track lies inside the root
    tracks = {e["tid"] for e in first}
    assert len(tracks) == 2             # `scrub` and `scrub-io`
    for e in first:
        if e["tid"] == root["tid"] and e is not root:
            assert root["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1
    (pw,) = [e for e in first if e["name"] == "parity write"]
    assert pw["args"]["rows"] == 2 and pw["args"]["written"] == 2
    # the account: the segments sum to the root span, to the microsecond
    # the ring keeps, and `other` is among them
    total = sum(seg(s) for s in SCRUB_SEGMENTS)
    assert abs(total * 1e6 - root["dur"]) < 1.5
    assert seg("other") > 0 and seg("codec_wait") > 0
    assert seg("parity_write") > 0 and seg("decompress") == 0
    assert w.m_passes.get() == 1
    assert w.m_bytes.get() == sum(map(len, blocks.values()))

    # a second pass with one block corrupted: healed from its sidecar,
    # under one `quarantine+heal` event that says how
    victim = next(iter(blocks))
    path, _ = m.find_block(Hash(victim))
    bad = bytearray(blocks[victim])
    bad[100] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(bad))
    before = sum(seg(s) for s in SCRUB_SEGMENTS)
    await _one_pass(w)
    second = events()[len(first):]
    (heal,) = [e for e in second if e["name"] == "quarantine+heal"]
    assert heal["args"] == {"blocks": 1, "how": "local_sidecar",
                            "local_sidecar": 1}
    # both codewords keep their members and their sidecars: no row is
    # asked for, so no `parity write` stands in the ring
    (ask2,) = [e for e in second if e["name"] == "parity ask"]
    assert ask2["args"]["lacking"] == 0
    assert not [e for e in second if e["name"] == "parity write"]
    (root2,) = [e for e in second if e["name"] == "scrub pass"]
    assert root2["args"]["corruptions"] == 1
    assert (root2["args"]["settled"], root2["args"]["rows"]) == (2, 2)
    grown = sum(seg(s) for s in SCRUB_SEGMENTS) - before
    assert abs(grown * 1e6 - root2["dur"]) < 1.5
    assert seg("heal") > 0 and w.m_passes.get() == 2
    # one event a batch or a pass: nothing here grows with the blocks
    assert len(second) <= 16
    await shutdown(systems)


@pytest.mark.asyncio
async def test_the_smoke_and_the_report_read_the_lanes_account_off_the_ring(
        tmp_path):
    """`chip_smoke.lane_faults` and `scrub_trace_report.lane_account`
    over the ring of a real pass: the first finds nothing wrong with it
    and something with a tampered one; the second sums a pass's `read
    files` events, whose slices' stages are their walls."""
    import copy
    import importlib.util

    import chip_smoke
    from garage_tpu.block import DataBlock
    from garage_tpu.block.repair import ScrubWorker
    from tests.test_block import make_block_cluster
    from tests.test_table import shutdown

    spec = importlib.util.spec_from_file_location(
        "scrub_trace_report",
        os.path.join(REPO, "scripts", "scrub_trace_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    systems, (m,) = await make_block_cluster(tmp_path, n=1, mode="1")
    for i in range(12):
        d = (b"text that shrinks " * 700 if i % 3 == 0 else b"") + os.urandom(
            8000 + i)
        await m.write_block(blake2s_sum(d), DataBlock.from_buffer(d, 1))
    w = ScrubWorker(m)
    await _one_pass(w)
    ring = m.codec.obs.timeline.snapshot()
    lane = {name: [e for e in ring if e["name"] == name]
            for name in ("read files", "read slice")}
    assert len(lane["read files"]) == 1 and len(lane["read slice"]) == 4
    assert chip_smoke.lane_faults(lane) == []
    bent = copy.deepcopy(lane)
    bent["read slice"][0]["args"]["pread_ms"] += 1.0
    # its wall, and the batch's sum
    assert len(chip_smoke.lane_faults(bent)) == 2
    bare = copy.deepcopy(lane)
    del bare["read files"][0]["args"]["open_ms"]     # a parent's event
    assert "no account" in chip_smoke.lane_faults(bare)[0]
    (root,) = [e for e in ring if e["name"] == "scrub pass"]
    acct = report.lane_account(ring, root)
    (ev,) = lane["read files"]
    assert acct["batches"] == 1 and acct["wall_ms"] == ev["dur"] / 1e3
    assert acct["direct"] + acct["buffered"] == 12
    assert acct["inflate_ms"] == ev["args"]["inflate_ms"] > 0
    assert abs(acct["unaccounted_us"]) <= 4.0
    assert acct["wall_ms"] * 1e3 <= root["dur"]
    await shutdown(systems)


# --- the transport's two stamped sections -------------------------------------

K, M = 4, 2
SIZES = (4096, 1000, 4096, 256, 2048, 77, 3000, 1025)


def _blocks(n, seed=0):
    rng = np.random.default_rng(seed)
    out = [rng.integers(0, 256, (SIZES[i % len(SIZES)],),
                        dtype=np.uint8).tobytes() for i in range(n)]
    return out, [Hash(hashlib.blake2s(b, digest_size=32).digest())
                 for b in out]


def _device_transport(reg=None):
    from garage_tpu.ops.tpu_codec import TpuCodec

    p = CodecParams(rs_data=K, rs_parity=M, block_size=4096)
    dev = TpuCodec(p, metrics=reg)
    pool = DevicePool(dev, pool_bytes=64 << 20, page_bytes=1024, metrics=reg)
    tr = DeviceTransport(dev, p, fallback=CpuCodec(p), observer=dev.obs,
                         metrics=reg, pool=pool)
    return tr, dev


def _scrub(tr, blocks, hashes):
    it = TransportItem("scrub", (blocks, hashes), len(blocks),
                       sum(map(len, blocks)), want_parity=True)
    tr.submit_items("scrub", [it])
    return it.future.result(timeout=120)


def test_compose_and_pool_adopt_lie_inside_adopt_and_collect():
    reg = MetricsRegistry()
    tr, dev = _device_transport(reg)
    try:
        blocks, hashes = _blocks(8)
        ok, _par = _scrub(tr, blocks, hashes)       # cold: 8 misses
        more, more_h = _blocks(4, seed=1)
        ok2, _par = _scrub(tr, blocks[:4] + more, hashes[:4] + more_h)
        assert ok.all() and ok2.all()
        evs = [e for e in dev.obs.timeline.snapshot() if e["ph"] == "X"]

        def inside(inner, outer):
            return (outer["ts"] <= inner["ts"]
                    and inner["ts"] + inner["dur"]
                    <= outer["ts"] + outer["dur"] + 1)

        for inner, outer in (("compose", "adopt scrub"),
                             ("pool adopt", "collect scrub")):
            ins = [e for e in evs if e["name"] == inner]
            outs = [e for e in evs if e["name"] == outer]
            assert len(ins) == len(outs) == 2
            for a, b in zip(ins, outs):
                assert a["tid"] == b["tid"] and inside(a, b), (a, b)
        composes = [e["args"] for e in evs if e["name"] == "compose"]
        assert composes == [{"miss_rows": 8, "resident_rows": 0},
                            {"miss_rows": 4, "resident_rows": 4}]
        adopts = [e["args"] for e in evs if e["name"] == "pool adopt"]
        pages = [sum(-(-len(b) // 1024) for b in bs)
                 for bs in (blocks, more)]
        assert adopts == [{"lanes": 8, "pages": pages[0]},
                          {"lanes": 4, "pages": pages[1]}]
        computes = [e["args"] for e in evs if e["name"] == "compute scrub"]
        assert all(c["variant"] in ("xla", "pallas") and c["lanes"] == 8
                   for c in computes) and len(computes) == 2
        # counted from the same stamps
        sub_s = reg.counter("transport_substage_seconds_total")
        sub_n = reg.counter("transport_substage_calls_total")
        for stage, name in (("compose", "compose"),
                            ("pool_adopt", "pool adopt")):
            assert sub_n.get(stage=stage) == 2
            us = sum(e["dur"] for e in evs if e["name"] == name)
            assert abs(sub_s.get(stage=stage) * 1e6 - us) < 2.5
        # one device program a section: the ratio anybody can read
        programs = reg.counter("pool_programs_total")
        assert programs.get(op="compose") == sub_n.get(stage="compose")
        assert programs.get(op="adopt") == sub_n.get(stage="pool_adopt")
        # and the five stages still sum to the round trips exactly
        staged = sum(sec for _n, sec, _b in tr.profiler.snapshot().values())
        assert staged == pytest.approx(tr.profiler._wall_ns / 1e9, abs=1e-9)
    finally:
        tr.shutdown()


# --- compiles put to the span they ran under ----------------------------------


def test_compile_lands_under_its_span_and_listener_registers_once():
    import jax
    import jax.numpy as jnp

    from garage_tpu.ops.tpu_codec import TpuCodec

    p = CodecParams(rs_data=K, rs_parity=M, block_size=4096)
    regs = [MetricsRegistry() for _ in range(3)]
    codecs = [TpuCodec(p, metrics=r) for r in regs]
    assert compile_listener.registrations == 1

    def compiles(reg, **labels):
        return reg.counter("codec_compiles_total").get(**labels)

    shape = (3, 7 + int(time.time()) % 89 + os.getpid() % 97)
    f = jax.jit(lambda x: x * 3 + 1)
    n0 = compile_listener.thread_compiles()
    tl = codecs[1].obs.timeline
    with tl.span("compose", "slot0"):
        f(jnp.ones(shape, jnp.float32)).block_until_ready()
    assert compile_listener.thread_compiles() > n0
    here = (compiles(regs[1], **{"where": "compose", "from": "built"})
            + compiles(regs[1], **{"where": "compose", "from": "cache"}))
    assert here >= 1
    assert regs[1].counter("codec_compile_seconds_total").get(
        where="compose") > 0
    # the span's own observer only; and the same shape again compiles
    # nothing
    for other in (regs[0], regs[2]):
        assert 'where="compose"' not in other.render()
    with tl.span("compose", "slot0"):
        f(jnp.ones(shape, jnp.float32)).block_until_ready()
    assert (compiles(regs[1], **{"where": "compose", "from": "built"})
            + compiles(regs[1], **{"where": "compose", "from": "cache"})
            == here)
    # under no span: every attached observer hears of it
    g = jax.jit(lambda x: x - 2)
    g(jnp.ones(shape, jnp.float32)).block_until_ready()
    for reg in regs:
        assert (compiles(reg, **{"where": "unspanned", "from": "built"})
                + compiles(reg, **{"where": "unspanned", "from": "cache"})
                >= 1)


def test_last_submit_compiled_means_a_compile():
    """The link profiler's `compile` stage is sourced from the listener:
    the first dispatch of a shape builds its program, the second does
    not, and a shape being new to a set plays no part."""
    tr, dev = _device_transport()
    try:
        rng = np.random.default_rng(5)      # 32 lanes x 16 KiB: no other
        blocks = [rng.integers(0, 256, (16000,), np.uint8).tobytes()
                  for _ in range(24)]         # test's shape
        hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
                  for b in blocks]
        _scrub(tr, blocks, hashes)
        cold = tr.profiler.snapshot()
        assert cold["compile"][0] == 1 and "dispatch" not in cold
        assert not hasattr(dev, "_dispatched_shapes")
        tr.pool.clear()                 # the same misses, the same shapes
        _scrub(tr, blocks, hashes)
        warm = tr.profiler.snapshot()
        assert warm["compile"][0] == 1 and warm["dispatch"][0] == 1
    finally:
        tr.shutdown()


# --- the profiler's clock and the ring's --------------------------------------


def test_profiler_trace_holds_gt_clock_and_spans_on_the_rings_clock(tmp_path):
    import jax

    from garage_tpu.ops.tpu_codec import TpuCodec

    dev = TpuCodec(CodecParams(rs_data=K, rs_parity=M, block_size=4096))
    tl = dev.obs.timeline
    assert tl.annotate is jax.profiler.TraceAnnotation
    t_begin = time.monotonic()
    try:
        jax.profiler.start_trace(str(tmp_path))
    except Exception as e:  # noqa: BLE001 — no profiler on this backend
        pytest.skip(f"the CPU profiler cannot start here: {e}")
    try:
        t0 = time.monotonic_ns()
        tl.mark_clock(t0)
        for _ in range(3):
            with tl.span("compose", "slot0"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    if time.monotonic() - t_begin > 20:
        pytest.skip("the CPU profiler took over 20 s for a 10 ms trace")
    paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert paths
    data = jax.profiler.ProfileData.from_file(paths[-1])
    marks = [e for pl in data.planes for ln in pl.lines for e in ln.events
             if e.name.startswith("gt:")]
    (clock,) = [e for e in marks if e.name == "gt:clock"]
    with warnings.catch_warnings():     # the binding's own deprecation
        warnings.simplefilter("ignore", DeprecationWarning)
        assert dict(clock.stats)["mono_ns"] == t0
    # profiler's clock − ring's clock, from the one annotation
    offset = clock.start_ns - t0
    spans = sorted((e for e in marks if e.name == "gt:compose"),
                   key=lambda e: e.start_ns)
    ring = [e for e in tl.snapshot() if e["name"] == "compose"]
    assert len(spans) == len(ring) == 3
    for prof, ev in zip(spans, ring):
        assert abs((prof.start_ns - offset) / 1e3 - ev["ts"]) < 1000
        assert abs(prof.duration_ns / 1e3 - ev["dur"]) < 1000

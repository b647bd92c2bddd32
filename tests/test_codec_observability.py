"""Dataplane observability: per-stage codec histograms, the
gate-decision event ring, heal/enqueue attribution, the slow-op log,
and the admin `codec info`/`codec events`/`slow-ops` commands.

Deterministic via the synthetic-link device (testing/synthetic_device.py):
the probe hook reports a configured rate, so the gate decision — and
therefore which events land in the ring — is exact.
"""

import asyncio
import hashlib

import numpy as np
import pytest

from garage_tpu.ops.codec import CodecParams
from garage_tpu.ops.feeder import CodecFeeder
from garage_tpu.ops.hybrid_codec import HybridCodec
from garage_tpu.testing.synthetic_device import SyntheticLinkCodec
from garage_tpu.utils.data import Hash
from garage_tpu.utils.metrics import MetricsRegistry

pytestmark = pytest.mark.asyncio


def _mk_batch(n=256, size=1 << 16, seed=0):
    rng = np.random.default_rng(seed)
    blocks = [rng.integers(0, 256, size, dtype=np.uint8).tobytes()
              for _ in range(n)]
    hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
              for b in blocks]
    return blocks, hashes


def _params(**kw):
    kw.setdefault("rs_data", 8)
    kw.setdefault("rs_parity", 4)
    return CodecParams(**kw)


def _scrub_through_feeder(hy, blocks, hashes):
    """One background scrub batch as the ScrubWorker submits it: the
    feeder refreshes the gate and hands an open-gate batch to the
    transport."""
    f = CodecFeeder(hy, slo_ms=1.0, max_batch_blocks=256)
    try:
        return f.submit_scrub(blocks, hashes, False).result(timeout=60)
    finally:
        f.shutdown()


def test_stage_histograms_and_bytes_by_side_scrapeable():
    """An open-gate scrub batch must leave per-stage histograms and
    bytes-by-side counters in the registry from which tpu_frac > 0 is
    computable — the acceptance bar of the observability tentpole."""
    params = _params()
    blocks, hashes = _mk_batch()
    reg = MetricsRegistry()
    dev = SyntheticLinkCodec(params, link_gibs=100.0, compute_real=True)
    hy = HybridCodec(params, device_codec=dev, metrics=reg)
    ok, _parity = _scrub_through_feeder(hy, blocks, hashes)
    assert ok.all()

    # scrapeable ratio: the counters carry the split
    assert hy.obs.bytes_total["tpu"] == 256 << 16
    assert hy.obs.tpu_frac() > 0.0
    text = reg.render()
    assert 'codec_bytes_total{side="tpu"}' in text
    assert "codec_stage_duration_seconds_bucket" in text

    # per-stage attribution exists for the gate's probe and the
    # transport's pipeline stages (the synthetic device has no internal
    # h2d/kernel refinement — a real TpuCodec adds those)
    stats = hy.obs.stage_stats()
    for stage in ("probe/tpu", "host_staging/tpu", "device_submit/tpu",
                  "sync_collect/tpu"):
        assert stage in stats and stats[stage]["count"] > 0, stats.keys()
    hy.close()


def test_gate_event_ring_open_and_hold():
    """The event ring must explain both gate outcomes with reasons."""
    params = _params()
    dev = SyntheticLinkCodec(params, link_gibs=50.0, compute_real=True)
    hy = HybridCodec(params, device_codec=dev)
    blocks, hashes = _mk_batch()
    _scrub_through_feeder(hy, blocks, hashes)
    kinds = {(e["kind"], e.get("reason")) for e in hy.obs.events_list()}
    assert ("probe", "ok") in kinds, kinds
    assert ("gate", "open") in kinds, kinds
    probe_evt = [e for e in hy.obs.events_list() if e["kind"] == "probe"][-1]
    assert probe_evt["gibs"] == pytest.approx(50.0)
    hy.close()

    # below-threshold link: the ring must carry the hold with the rate
    p2 = _params(hybrid_min_link_gibs=1.0)
    dev2 = SyntheticLinkCodec(p2, link_gibs=0.001, compute_real=True)
    hy2 = HybridCodec(p2, device_codec=dev2)
    ok, _parity = _scrub_through_feeder(hy2, blocks, hashes)
    assert ok.all()
    holds = [e for e in hy2.obs.events_list()
             if e["kind"] == "gate" and e["reason"] == "hold"]
    assert holds, hy2.obs.events_list()
    assert holds[-1]["gibs"] == pytest.approx(0.001)
    assert hy2.obs.bytes_total["tpu"] == 0
    hy2.close()


async def test_gate_and_link_gibs_reported_on_the_feeder_road(tmp_path):
    """`codec info`, the admin stats and the codec_link_gibs gauge name
    the gate's verdict on a codec that has only ever been driven
    through CodecFeeder (every node): the verdict's telemetry is
    written where the probe is taken, whoever asked for it."""
    from garage_tpu.admin.handler import AdminRpcHandler

    g = await _mk_garage(tmp_path)
    try:
        for link, gate in ((100.0, "open"), (0.001, "hold")):
            params = _params()
            dev = SyntheticLinkCodec(params, link_gibs=link,
                                     compute_real=True)
            hy = HybridCodec(params, device_codec=dev)
            assert hy.info()["gate"] is None     # nothing probed yet
            g.block_manager.codec = hy
            blocks, hashes = _mk_batch(n=32)
            ok, _parity = await asyncio.to_thread(
                _scrub_through_feeder, hy, blocks, hashes)
            assert ok.all()
            info = hy.info()
            assert info["gate"] == gate
            assert info["link_gibs"] == pytest.approx(link)
            assert info["link_stages"]
            # the engine's three fields went with it
            assert not [k for k in info if "blocks" in k or k == "window"]
            admin = AdminRpcHandler(g, register_endpoint=False)
            stats = (await admin._cmd_stats({}))["codec"]
            assert stats["gate"] == gate
            assert stats["link_gibs"] == pytest.approx(link)
            gauge, = [ln for ln in g.system.metrics.render().splitlines()
                      if ln.startswith("codec_link_gibs ")]
            assert float(gauge.split()[-1]) == pytest.approx(link)
            hy.close()
    finally:
        await g.shutdown()


def test_event_ring_is_bounded():
    from garage_tpu.ops.observer import CodecObserver

    obs = CodecObserver(ring_size=8)
    for i in range(100):
        obs.event("probe", reason="ok", i=i)
    evs = obs.events_list()
    assert len(evs) == 8
    assert evs[-1]["i"] == 99 and evs[0]["i"] == 92
    # seq keeps counting even as the ring drops old entries
    assert evs[-1]["seq"] == 100


def test_fused_latch_sync_failure_demotes(monkeypatch):
    """Sync-time kernel failures (surfacing at np.asarray in the
    transport's collect) must feed the fused-scrub demotion
    latch, and the failure counter must reset only after a successful
    host-side materialization."""
    from garage_tpu.ops.tpu_codec import PALLAS_MAX_TRANSIENT_FAILS, TpuCodec

    tpu = TpuCodec(_params(batch_blocks=32))
    assert tpu._pallas_fused_ok

    # transient sync failures from the pallas variant accumulate...
    for i in range(PALLAS_MAX_TRANSIENT_FAILS - 1):
        tpu.note_sync_failure(RuntimeError("UNAVAILABLE: connection reset"),
                              variant="pallas")
        assert tpu._pallas_fused_fails == i + 1
        assert tpu._pallas_fused_ok
    # ...a successful materialization of a PALLAS submission resets them
    tpu.note_sync_success(variant="pallas")
    assert tpu._pallas_fused_fails == 0

    # an xla-variant sync failure must NOT touch the pallas latch
    tpu.note_sync_failure(RuntimeError("UNAVAILABLE"), variant="xla")
    assert tpu._pallas_fused_fails == 0 and tpu._pallas_fused_ok

    # consecutive pallas sync failures demote for good
    for _ in range(PALLAS_MAX_TRANSIENT_FAILS):
        tpu.note_sync_failure(RuntimeError("DEADLINE_EXCEEDED"),
                              variant="pallas")
    assert not tpu._pallas_fused_ok
    demotes = [e for e in tpu.obs.events_list()
               if e["kind"] == "fused_demote"]
    assert demotes and demotes[-1]["reason"] == "transient_limit"

    # a permanent marker demotes instantly
    tpu2 = TpuCodec(_params(batch_blocks=32))
    tpu2.note_sync_failure(RuntimeError("Mosaic not implemented"),
                           variant="pallas")
    assert not tpu2._pallas_fused_ok

    # submit-time success must NOT reset the counter (the old bug: the
    # reset fired before the kernel provably ran)
    tpu3 = TpuCodec(_params(batch_blocks=32))
    tpu3._pallas_fused_fails = 3
    blocks, hashes = _mk_batch(16, size=512)
    ok, _parity = tpu3.scrub_encode_batch(blocks, hashes)
    assert ok.all()
    # the sync ran the XLA variant (16 lanes % 128 != 0 → no pallas), so
    # the PALLAS counter must be untouched by its success
    assert tpu3.last_submit_variant == "xla"
    assert tpu3._pallas_fused_fails == 3


def test_hybrid_collect_reports_sync_failure_to_device():
    """A device whose submissions die at sync time must (a) not fail the
    scrub (CPU absorbs) and (b) have the failure reported back through
    note_sync_failure with the submission's variant."""
    params = _params()
    noted = []

    class _SyncFailDevice(SyntheticLinkCodec):
        last_submit_variant = "pallas"

        def scrub_collect(self, out, fetch_parity):
            raise RuntimeError("UNAVAILABLE: sync failed")

        def note_sync_failure(self, e, variant=None):
            noted.append((type(e).__name__, variant))

        def note_sync_success(self, variant=None):
            noted.append(("ok", variant))

    blocks, hashes = _mk_batch(n=32)
    dev = _SyncFailDevice(params, link_gibs=100.0)
    hy = HybridCodec(params, device_codec=dev)
    ok, _parity = _scrub_through_feeder(hy, blocks, hashes)
    assert ok.all(), "CPU did not absorb the failure"
    assert dev.array_submissions == 1
    assert noted == [("RuntimeError", "pallas")], noted
    kinds = {e["kind"] for e in hy.obs.events_list()}
    assert {"transport_error", "transport_fallback"} <= kinds
    assert hy.obs.bytes_total == {"cpu": 32 << 16, "tpu": 0}
    hy.close()


def test_slow_op_log_always_on():
    """Top-N slowest spans retained with NO trace_sink configured."""
    import time

    from garage_tpu.utils.tracing import SlowOpLog, init_tracing

    tr = init_tracing(None, b"\x07" * 32)
    assert not tr.enabled
    with tr.span("Block read", block="cafe"):
        time.sleep(0.02)
    with tr.span("Block read", block="beef"):
        pass  # sub-threshold: must not be retained
    snap = tr.slow.snapshot()
    assert len(snap) == 1 and snap[0]["name"] == "Block read"
    assert snap[0]["seconds"] >= 0.02
    assert snap[0]["attrs"]["block"] == "cafe"
    assert tr.slow.max_seconds() >= 0.02

    # bounded top-N: only the slowest `size` survive, slowest first
    log = SlowOpLog(size=4)
    for i in range(20):
        log.note(f"op{i}", 0.01 + i * 0.01, {})
    snap = log.snapshot()
    assert [r["name"] for r in snap] == ["op19", "op18", "op17", "op16"]


async def _mk_garage(tmp_path, codec_cfg=None):
    from garage_tpu.model import Garage
    from garage_tpu.rpc.layout import ClusterLayout, NodeRole
    from garage_tpu.utils.config import config_from_dict

    cfg = {
        "metadata_dir": str(tmp_path / "meta"),
        "data_dir": str(tmp_path / "data"),
        "replication_mode": "none",
        "rpc_bind_addr": "127.0.0.1:0",
        "rpc_secret": "obs-test",
        "db_engine": "memory",
        "bootstrap_peers": [],
    }
    if codec_cfg:
        cfg["codec"] = codec_cfg
    g = Garage(config_from_dict(cfg))
    await g.system.netapp.listen("127.0.0.1:0")
    lay = g.system.layout
    lay.stage_role(bytes(g.system.id), NodeRole("dc1", 1000))
    lay.apply_staged_changes()
    g.system.layout = ClusterLayout.decode(lay.encode())
    g.system._rebuild_ring()
    return g


async def test_admin_codec_info_events_and_slow_ops(tmp_path):
    """The admin command surface: `codec info` explains the codec,
    `codec events` returns the ring, `slow_ops` the retained spans —
    after a scrub pass through the node's own metrics registry."""
    from garage_tpu.admin.handler import AdminRpcHandler

    g = await _mk_garage(tmp_path)
    try:
        # swap in a hybrid codec wired to the SYSTEM registry with the
        # synthetic device — the deterministic stand-in for a live TPU
        params = _params()
        dev = SyntheticLinkCodec(params, link_gibs=100.0,
                                 compute_real=True)
        hy = HybridCodec(params, device_codec=dev,
                         metrics=g.system.metrics,
                         tracer=g.system.tracer)
        g.block_manager.codec = hy
        blocks, hashes = _mk_batch()
        await asyncio.to_thread(_scrub_through_feeder, hy, blocks, hashes)

        admin = AdminRpcHandler(g, register_endpoint=False)
        info = await admin._cmd_codec_info({})
        assert info["backend"] == "HybridCodec"
        assert info["device_attached"] is True
        assert info["gate"] == "open"
        assert info["bytes"]["tpu"] > 0
        assert info["tpu_frac"] > 0
        assert info["params"]["rs_data"] == 8
        assert any(k.startswith("device_submit/") for k in info["stages"])

        events = await admin._cmd_codec_events({})
        assert events, "gate-decision log empty after a scrub pass"
        assert any(e["kind"] == "gate" and e["reason"] == "open"
                   for e in events)
        limited = await admin._cmd_codec_events({"limit": 2})
        assert len(limited) == 2 and limited == events[-2:]

        # /metrics carries the codec families end-to-end
        text = g.system.metrics.render()
        assert 'codec_bytes_total{side="tpu"}' in text
        assert "codec_stage_duration_seconds_bucket" in text

        # slow-op log through the real admin command (block write spans
        # feed it even with no trace_sink): force one slow op
        g.system.tracer.slow.note("Block write", 0.5, {"block": "aa"})
        slow = await admin._cmd_slow_ops({"limit": 5})
        assert slow and slow[0]["name"] == "Block write"
    finally:
        await g.shutdown()


async def test_metrics_endpoint_serves_codec_families(tmp_path):
    """End-to-end /metrics: a node that ran a scrub pass with the
    synthetic device exposes per-stage histograms and bytes-by-side
    counters from which tpu_frac > 0 is computable (acceptance
    criterion)."""
    import aiohttp

    from garage_tpu.api.admin_server import AdminApiServer

    g = await _mk_garage(tmp_path)
    srv = None
    try:
        params = _params()
        dev = SyntheticLinkCodec(params, link_gibs=100.0,
                                 compute_real=True)
        hy = HybridCodec(params, device_codec=dev,
                         metrics=g.system.metrics,
                         tracer=g.system.tracer)
        g.block_manager.codec = hy
        blocks, hashes = _mk_batch()
        await asyncio.to_thread(_scrub_through_feeder, hy, blocks, hashes)

        srv = AdminApiServer(g)
        await srv.start("127.0.0.1:0")
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{srv.port}/metrics"
            ) as r:
                assert r.status == 200
                text = await r.text()
        # tpu_frac computable from the exposition alone
        cpu_b, tpu_b = 0.0, None   # a side with no bytes has no series
        for line in text.splitlines():
            if line.startswith('codec_bytes_total{side="cpu"}'):
                cpu_b = float(line.split()[-1])
            if line.startswith('codec_bytes_total{side="tpu"}'):
                tpu_b = float(line.split()[-1])
        assert tpu_b is not None, "families missing"
        assert tpu_b > 0 and tpu_b / (cpu_b + tpu_b) > 0
        assert "codec_stage_duration_seconds_bucket" in text
        assert "tracer_slow_op_max_seconds" in text
        # the manager-registered gauges read THROUGH block_manager.codec,
        # so they track the swapped-in hybrid codec, not the boot codec
        assert "codec_device_attached 1" in text
        assert "codec_tpu_frac" in text
    finally:
        if srv is not None:
            await srv.stop()
        await g.shutdown()


async def test_resync_enqueue_attribution(tmp_path):
    """Enqueue sources are counted — the seam that distinguishes
    fallback-kick heals (layout_sweep) from organic ones (round-5 heal
    non-repro)."""
    from garage_tpu.utils.data import blake2s_sum

    g = await _mk_garage(tmp_path)
    try:
        data = b"attribution-test" * 100
        h = blake2s_sum(data)
        g.block_resync.put_to_resync(h, 60.0, source="layout_sweep")
        g.block_resync.put_to_resync(h, 60.0, source="incref")
        g.block_resync.put_to_resync(h, 60.0, source="incref")
        assert g.block_resync.enqueue_counts == {
            "layout_sweep": 1, "incref": 2}
        assert g.block_resync.m_enqueue.get(source="incref") == 2
        text = g.system.metrics.render()
        assert 'block_resync_enqueue_total{source="incref"} 2' in text
    finally:
        await g.shutdown()

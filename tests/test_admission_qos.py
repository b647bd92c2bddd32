"""Multi-tenant QoS at the front door (ISSUE 12): WDRR fair queueing,
CoDel adaptive watermarks, cluster-aware admission, client deadlines,
streaming-body byte accounting, and long-poll slot parking.

Deterministic where possible: CoDel transitions run on an injected
clock, WDRR invariants drive the gate object directly, the gossiped-
pressure shed path runs on a small faultless SimCluster."""

import asyncio
import math

import pytest

from garage_tpu.api.admission import (
    AdmissionGate,
    classify_tenant,
)
from garage_tpu.api.common import body_claim, client_deadline_budget
from garage_tpu.rpc.system import NodeStatus
from garage_tpu.utils.config import ConfigError, config_from_dict
from garage_tpu.utils.metrics import MetricsRegistry
from garage_tpu.utils.overload import LoadGovernor, OverloadTunables

pytestmark = pytest.mark.asyncio


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeRequest:
    """Just enough of an aiohttp request for classification/claims."""

    def __init__(self, headers=None, path="/", query=None):
        self.headers = dict(headers or {})
        self.path = path
        self.query = dict(query or {})


# --- tenant classification ---------------------------------------------


def test_classify_tenant_key_then_bucket_then_anon():
    r = FakeRequest(headers={
        "Authorization": "AWS4-HMAC-SHA256 Credential=GKabc123/20260804/"
                         "garage/s3/aws4_request, SignedHeaders=h, "
                         "Signature=sig"})
    assert classify_tenant(r) == "GKabc123"
    r = FakeRequest(query={"X-Amz-Credential": "GKpre/20260804/garage"})
    assert classify_tenant(r) == "GKpre"
    assert classify_tenant(FakeRequest(path="/mybkt/key")) == "bucket:mybkt"
    assert classify_tenant(FakeRequest(path="/")) == "anon"
    # vhost-style: the caller's parsed bucket wins over the path (whose
    # first segment is the object KEY for vhost requests)
    assert classify_tenant(FakeRequest(path="/logs/a.txt"),
                           bucket="realbkt") == "bucket:realbkt"


# --- WDRR fairness invariants ------------------------------------------


async def test_wdrr_small_tenant_not_stuck_behind_big_request():
    """Byte-sized deficits: a queued cheap request from tenant C
    dispatches before tenant B's expensive head even though B queued
    first — and B is still served eventually (no starvation)."""
    tun = OverloadTunables(max_inflight=1, wdrr_quantum_bytes=100,
                          wdrr_request_cost=0, tenant_queue_wait=5.0,
                          codel_target=0)
    gate = AdmissionGate(tun)
    hold = gate.try_admit(tenant="A")
    assert hold is not None
    order = []

    async def want(tenant, nbytes):
        tok, verdict = await gate.admit(nbytes, tenant=tenant)
        assert tok is not None, verdict
        order.append((tenant, nbytes))
        await asyncio.sleep(0)        # let the next release interleave
        tok.release()

    tasks = [asyncio.ensure_future(want("B", 250)),
             asyncio.ensure_future(want("C", 50))]
    await asyncio.sleep(0.01)         # both queued behind the held slot
    assert gate.stats()["queued"] == 2
    hold.release()                    # WDRR takes over
    await asyncio.gather(*tasks)
    # C's 50-byte request fit the first quantum; B's 250-byte head had
    # to accumulate deficit across visits
    assert order[0][0] == "C"
    assert ("B", 250) in order


async def test_per_tenant_shed_isolation_and_starvation_freedom():
    """An abuser at its fair share sheds typed (over_share) while a
    well-behaved tenant is admitted — and a saturating abuser can never
    starve the other tenant's requests."""
    tun = OverloadTunables(max_inflight=2, tenant_queue_wait=5.0,
                          codel_target=0)
    reg = MetricsRegistry()
    gate = AdmissionGate(tun, metrics=reg)
    a1 = gate.try_admit(tenant="abuser")
    a2 = gate.try_admit(tenant="abuser")
    assert a1 is not None and a2 is not None

    # well-behaved queues (under share), so the abuser is now over ITS
    # share (2 >= ceil(2/2)) and sheds — per-tenant, not gate-wide
    well_results = []

    async def well_request():
        tok, verdict = await gate.admit(0, tenant="well")
        well_results.append(verdict)
        assert tok is not None
        tok.release()

    w = asyncio.ensure_future(well_request())
    await asyncio.sleep(0.01)
    tok, verdict = await gate.admit(0, tenant="abuser")
    assert tok is None and verdict == "over_share"
    assert gate.m_admission.get(verdict="over_share") == 1.0
    assert gate.m_tenant_shed.get(tenant="abuser") == 1.0
    assert gate.m_tenant_shed.get(tenant="well") == 0.0

    # a released slot goes to the queued well tenant, not the abuser
    a1.release()
    await asyncio.wait_for(w, 2.0)
    assert well_results == ["admit"]

    # starvation-freedom under a closed-loop saturating abuser: N well
    # requests all get through while the abuser keeps re-acquiring
    stop = [False]

    async def abuser_loop():
        held = [a2]
        while not stop[0]:
            t = gate.try_admit(tenant="abuser")
            if t is not None:
                held.append(t)
            if held:
                held.pop(0).release()
            await asyncio.sleep(0)
        for t in held:
            t.release()

    ab = asyncio.ensure_future(abuser_loop())
    for _ in range(10):
        tok, verdict = await asyncio.wait_for(
            gate.admit(0, tenant="well"), 2.0)
        assert tok is not None, verdict
        tok.release()
    stop[0] = True
    await ab


async def test_cancelled_waiter_releases_granted_slot():
    """A queued client that disconnects in the same window in which
    _dispatch granted its slot must not leak that slot forever."""
    gate = AdmissionGate(OverloadTunables(max_inflight=1,
                                          tenant_queue_wait=5.0,
                                          codel_target=0))
    hold = gate.try_admit(tenant="a")
    task = asyncio.ensure_future(gate.admit(0, tenant="b"))
    await asyncio.sleep(0.01)          # queued behind the held slot
    hold.release()                     # grants b's future synchronously
    task.cancel()                      # ...but the client already gave up
    with pytest.raises(asyncio.CancelledError):
        await task
    assert gate.inflight == 0          # the granted slot came back
    tok = gate.try_admit(tenant="c")
    assert tok is not None
    tok.release()


async def test_large_body_dispatch_fast_forwards():
    """A queued request whose byte cost is many quanta must be granted
    in one fast-forwarded step, not O(cost/quantum) synchronous WDRR
    rounds on the event loop."""
    import time as _time

    tun = OverloadTunables(max_inflight=1, wdrr_quantum_bytes=100,
                          wdrr_request_cost=0, tenant_queue_wait=5.0,
                          codel_target=0)
    gate = AdmissionGate(tun)
    hold = gate.try_admit(tenant="A")
    big = asyncio.ensure_future(gate.admit(50_000_000, tenant="B"))
    await asyncio.sleep(0.01)
    t0 = _time.perf_counter()
    hold.release()                     # 500k quanta owed: one step
    tok, verdict = await asyncio.wait_for(big, 2.0)
    assert tok is not None, verdict
    assert _time.perf_counter() - t0 < 0.5
    tok.release()


async def test_queue_bounds_shed_typed():
    tun = OverloadTunables(max_inflight=1, tenant_queue_len=2,
                          tenant_queue_wait=0.05, codel_target=0)
    gate = AdmissionGate(tun)
    hold = gate.try_admit(tenant="other")
    waiters = [asyncio.ensure_future(gate.admit(0, tenant="B"))
               for _ in range(2)]
    await asyncio.sleep(0.01)
    # the tenant's queue is full: the third request sheds queue_full
    # IMMEDIATELY (no wait)
    tok, verdict = await gate.admit(0, tenant="B")
    assert tok is None and verdict == "queue_full"
    # the queued two time out typed (bounded wait, no silent hang)
    for fut in waiters:
        tok, verdict = await fut
        assert tok is None and verdict == "queue_timeout"
    assert gate.stats()["queued"] == 0
    hold.release()


# --- CoDel adaptive watermark ------------------------------------------


def _sojourn_release(gate, clk, sojourn):
    tok = gate.try_admit(tenant="t")
    assert tok is not None
    clk.advance(sojourn)
    tok.release()


def test_codel_tightens_on_drift_and_relaxes_after():
    clk = FakeClock()
    tun = OverloadTunables(max_inflight=16, codel_target=0.1,
                          codel_interval=1.0)
    gate = AdmissionGate(tun, clock=clk)
    assert gate.limit == 16
    # latency above target, sustained past the interval → tighten
    for _ in range(8):
        _sojourn_release(gate, clk, 0.3)
    assert gate.limit < 16
    tightened = gate.limit
    # keep drifting → keeps tightening, but never below the floor
    for _ in range(100):
        _sojourn_release(gate, clk, 0.3)
    assert gate._codel_floor() <= gate.limit <= tightened
    assert gate.limit >= max(1, tun.max_inflight // 8)
    # latency back under target → relaxes toward the ceiling, paced by
    # the interval (not a single-sample snap)
    _sojourn_release(gate, clk, 0.01)
    after_one = gate.limit
    for _ in range(100):
        clk.advance(0.3)
        _sojourn_release(gate, clk, 0.01)
    assert gate.limit == 16
    assert after_one <= 16
    # a single above-target blip does NOT tighten (needs an interval)
    _sojourn_release(gate, clk, 0.3)
    assert gate.limit == 16


def test_codel_excludes_client_paced_durations():
    """Large uploads and streamed downloads take as long as the CLIENT
    takes — a healthy big-object workload must not strangle the limit."""
    clk = FakeClock()
    tun = OverloadTunables(max_inflight=16, codel_target=0.1,
                          codel_interval=1.0)
    gate = AdmissionGate(tun, clock=clk)
    # big declared bodies: slow by nature, excluded from the law
    for _ in range(50):
        tok = gate.try_admit(4 << 20, tenant="t")
        clk.advance(10.0)
        tok.release()
    assert gate.limit == 16
    # streamed-GET tokens opt out explicitly (exclude_sojourn)
    for _ in range(50):
        tok = gate.try_admit(tenant="t")
        tok.exclude_sojourn()
        clk.advance(10.0)
        tok.release()
    assert gate.limit == 16
    # a small body TRICKLED slowly: the sojourn anchor moves to body
    # completion, so only the post-body service time feeds the law
    for _ in range(50):
        tok = gate.try_admit(100, tenant="t")
        clk.advance(10.0)              # client-paced trickle
        tok.body_done()
        clk.advance(0.01)              # actual service: fast
        tok.release()
    assert gate.limit == 16
    # ...while small-request drift still tightens (the latency canary)
    for _ in range(8):
        _sojourn_release(gate, clk, 0.3)
    assert gate.limit < 16


def test_codel_disabled_keeps_static_watermark():
    clk = FakeClock()
    gate = AdmissionGate(OverloadTunables(max_inflight=4, codel_target=0),
                         clock=clk)
    for _ in range(50):
        _sojourn_release(gate, clk, 10.0)
    assert gate.limit == 4


def test_occupancy_uses_effective_limit():
    clk = FakeClock()
    tun = OverloadTunables(max_inflight=16, codel_target=0.1,
                          codel_interval=1.0, max_inflight_bytes=0)
    gate = AdmissionGate(tun, clock=clk)
    for _ in range(50):
        _sojourn_release(gate, clk, 0.5)
    limit = gate.limit
    assert limit < 16
    toks = [gate.try_admit(tenant="t") for _ in range(limit)]
    assert all(t is not None for t in toks)
    assert gate.occupancy() == pytest.approx(1.0)
    assert gate.try_admit(tenant="t") is None     # tightened limit binds
    for t in toks:
        t.release()


# --- load-derived Retry-After ------------------------------------------


def test_retry_after_tracks_load():
    tun = OverloadTunables(max_inflight=4, retry_after=1, retry_after_max=30,
                          codel_target=0)
    gate = AdmissionGate(tun)
    assert gate.retry_after_hint() == 1            # idle → base
    toks = [gate.try_admit(tenant="t") for _ in range(4)]
    assert gate.retry_after_hint() >= 3            # full gate → scaled
    gate.pressure_fn = lambda: 2.0
    hot = gate.retry_after_hint()
    assert hot >= 5
    gate.pressure_fn = lambda: 100.0               # clamped, not absurd
    assert gate.retry_after_hint() <= 30
    gate.pressure_fn = lambda: 1 / 0               # dead signal ≠ crash
    assert gate.retry_after_hint() >= 1
    for t in toks:
        t.release()


# --- client deadlines (X-Request-Timeout) ------------------------------


def test_client_deadline_clamps_never_extends():
    assert client_deadline_budget(30.0, FakeRequest()) == 30.0
    r = FakeRequest(headers={"X-Request-Timeout": "5"})
    assert client_deadline_budget(30.0, r) == 5.0
    r = FakeRequest(headers={"X-Request-Timeout": "100"})
    assert client_deadline_budget(30.0, r) == 30.0   # never extends
    # deadlines disabled: the client may still arm its own
    assert client_deadline_budget(None, r) == 100.0
    # malformed / non-finite / non-positive ignored
    for bad in ("abc", "", "-1", "0", "nan", "inf"):
        r = FakeRequest(headers={"X-Request-Timeout": bad})
        assert client_deadline_budget(30.0, r) == 30.0, bad


async def test_s3_client_deadline_sheds_typed(tmp_path):
    """An absurdly tight X-Request-Timeout turns into the typed 503
    DeadlineExceeded answer (Retry-After + RequestId), not a hang or an
    untyped 500."""
    import xml.etree.ElementTree as ET

    from test_s3_api import make_api_cluster, stop_all

    garages, server, client, _key = await make_api_cluster(tmp_path)
    try:
        st, _h, _b = await client.req("PUT", "/dlbkt")
        assert st == 200
        st, hdrs, body = await client.req(
            "PUT", "/dlbkt/obj", body=b"x" * 1024,
            headers={"X-Request-Timeout": "0.000001"})
        assert st == 503
        root = ET.fromstring(body)
        assert root.findtext("Code") == "DeadlineExceeded"
        assert root.findtext("RequestId")
        assert "Retry-After" in hdrs
        # malformed header is ignored: the request succeeds normally
        st, _h, _b = await client.req(
            "PUT", "/dlbkt/obj", body=b"x" * 1024,
            headers={"X-Request-Timeout": "bogus"})
        assert st == 200
    finally:
        await stop_all(garages, server)


# --- streaming-body byte accounting ------------------------------------


def test_body_claim_chunked_vs_declared():
    tun = OverloadTunables(streaming_body_estimate=1000)
    assert body_claim(tun, FakeRequest(
        headers={"Content-Length": "123"})) == (123, False)
    assert body_claim(tun, FakeRequest(
        headers={"Transfer-Encoding": "chunked"})) == (1000, True)
    assert body_claim(tun, FakeRequest()) == (0, False)
    # malformed Content-Length claims nothing rather than crashing
    assert body_claim(tun, FakeRequest(
        headers={"Content-Length": "zz"})) == (0, False)


async def test_estimated_bytes_reconcile_up_and_down():
    tun = OverloadTunables(max_inflight=0, max_inflight_bytes=10000,
                          streaming_body_estimate=1000, codel_target=0)
    gate = AdmissionGate(tun)
    tok, verdict = await gate.admit(1000, tenant="t", estimated=True)
    assert tok is not None and gate.inflight_bytes == 1000
    tok.note_body_bytes(600)          # under the claim: no change yet
    assert gate.inflight_bytes == 1000
    tok.note_body_bytes(600)          # 1200 observed: claim grows live
    assert gate.inflight_bytes == 1200
    tok.body_done()
    assert gate.inflight_bytes == 1200
    tok.release()
    assert gate.inflight_bytes == 0
    # over-estimate reconciles DOWN when the body ends
    tok, _v = await gate.admit(1000, tenant="t", estimated=True)
    tok.note_body_bytes(100)
    tok.body_done()
    assert gate.inflight_bytes == 100
    tok.release()
    assert gate.inflight_bytes == 0


# --- long-poll slot parking --------------------------------------------


async def test_longpoll_park_frees_the_watermark():
    gate = AdmissionGate(OverloadTunables(max_inflight=1, codel_target=0))
    poll = gate.try_admit(tenant="poller")
    assert poll is not None
    assert gate.try_admit(tenant="put") is None    # gate full
    poll.park()
    assert gate.inflight == 0 and gate.longpoll_parked == 1
    put = gate.try_admit(tenant="put")
    assert put is not None                         # freed while parked
    poll.unpark()                                  # transient overshoot OK
    assert gate.inflight == 2 and gate.longpoll_parked == 0
    poll.release()
    put.release()
    assert gate.inflight == 0
    # releasing while parked balances the parked pool too
    poll = gate.try_admit(tenant="poller")
    poll.park()
    poll.release()
    assert gate.longpoll_parked == 0 and gate.inflight == 0


async def test_longpoll_pool_bounded_and_counts_toward_share():
    """The parked pool is CAPPED (a full pool means the poll keeps its
    admission slot — poll concurrency stays gate-bounded either way),
    and parked polls count as tenant usage in the fair-share check."""
    tun = OverloadTunables(max_inflight=2, longpoll_max_parked=1,
                          codel_target=0)
    gate = AdmissionGate(tun)
    p1 = gate.try_admit(tenant="a")
    p1.park()
    assert gate.longpoll_parked == 1 and gate.inflight == 0
    p2 = gate.try_admit(tenant="a")
    p2.park()                          # pool full: keeps its slot
    assert gate.longpoll_parked == 1 and gate.inflight == 1
    hold = gate.try_admit(tenant="b")  # gate now contended
    tok, verdict = await gate.admit(0, tenant="a")
    assert tok is None and verdict == "over_share"   # parked counts
    p2.unpark()                        # never parked: no-op
    for t in (p1, p2, hold):
        t.release()
    assert gate.inflight == 0 and gate.longpoll_parked == 0
    # default cap derives from the inflight ceiling
    gate = AdmissionGate(OverloadTunables(max_inflight=3))
    assert gate._longpoll_cap() == 12


async def test_queue_wait_clamped_to_deadline_budget():
    """Time queued at admission SPENDS the request's deadline budget:
    a 0.1 s budget must not wait 10 s in the WDRR queue on top."""
    import time as _time

    from garage_tpu.utils.tracing import deadline_scope

    tun = OverloadTunables(max_inflight=1, tenant_queue_wait=10.0,
                          codel_target=0)
    gate = AdmissionGate(tun)
    hold = gate.try_admit(tenant="a")
    t0 = _time.monotonic()
    with deadline_scope(0.1):
        tok, verdict = await gate.admit(0, tenant="b")
    assert tok is None and verdict == "queue_timeout"
    assert _time.monotonic() - t0 < 1.0
    hold.release()


async def test_k2v_longpoll_parks_admission_slot(tmp_path):
    """A K2V poll_item with the gate capped at ONE slot must not brown
    out admission: while it waits, the slot is parked and a write is
    admitted — which is exactly what wakes the poll up."""
    from test_k2v_api import make_k2v

    g, srv, c, _k = await make_k2v(tmp_path)
    try:
        gate = g.admission
        gate.tun.max_inflight = 1
        await c.insert_item("pp", "ss", b"first")
        item = await c.read_item("pp", "ss")

        poll = asyncio.ensure_future(
            c.poll_item("pp", "ss", str(item.token), timeout=10.0))
        for _ in range(100):
            if gate.longpoll_parked == 1:
                break
            await asyncio.sleep(0.02)
        assert gate.longpoll_parked == 1
        assert gate.inflight == 0      # the single slot is free again

        # the write is admitted through the SAME 1-slot gate and wakes
        # the parked poll
        await c.insert_item("pp", "ss", b"second", token=str(item.token))
        got = await asyncio.wait_for(poll, 5.0)
        assert got is not None and got.values == [b"second"]
        assert gate.longpoll_parked == 0
    finally:
        await srv.stop()
        await g.shutdown()


# --- cluster-aware admission (gossiped governor_pressure) ---------------


def test_node_status_gossips_governor_pressure():
    st = NodeStatus.unpack({"hostname": "old-peer"})
    assert st.governor_pressure is None            # old peers: unknown
    st = NodeStatus(governor_pressure=1.25)
    assert NodeStatus.unpack(st.pack()).governor_pressure == 1.25


async def test_gossiped_pressure_sheds_at_gateway(tmp_path):
    """SimCluster: pin one storage node's governor pressure hot, gossip
    it, and a request whose bucket lives on that node is shed
    remote_pressure at the gateway — whose own gate is UNDER its
    watermark — then admitted again after heal."""
    import xml.etree.ElementTree as ET

    import aiohttp

    from garage_tpu.testing.local_cluster import S3
    from garage_tpu.testing.sim_cluster import SimCluster

    cluster = SimCluster(
        tmp_path, n_storage=3, n_zones=3,
        extra_cfg={"api": {"max_inflight": 8}})
    await cluster.start(faults=False)
    try:
        g0 = cluster.garages[0]
        gate = g0.admission
        async with aiohttp.ClientSession() as session:
            s3 = S3(session, cluster.port, cluster.key_id, cluster.secret)
            st, _b, _h = await s3.req("PUT", "/pressbkt")
            assert st == 200
            # first object request teaches the probe the placement
            st, _b, _h = await s3.req("PUT", "/pressbkt/seed", b"x" * 512)
            assert st == 200
            bid = g0.admission_probe._ids.get("pressbkt")
            assert bid is not None

            nodes = g0.system.ring.get_nodes(
                bid, g0.system.replication_mode.replication_factor)
            victim = next(
                g for i, g in enumerate(cluster.garages)
                if i != 0 and any(bytes(g.system.id) == bytes(n)
                                  for n in nodes))
            victim.governor.add_signal("hot", lambda: 2.0)
            await victim.system.advertise_status()
            assert g0.system.peer_pressure(victim.system.id) >= 1.5

            assert gate.inflight < gate.limit      # locally idle
            st, rb, hdrs = await s3.req("PUT", "/pressbkt/blocked",
                                        b"y" * 512)
            assert st == 503
            assert ET.fromstring(rb).findtext("Code") == "SlowDown"
            assert "Retry-After" in hdrs
            assert gate.m_admission.get(verdict="remote_pressure") >= 1
            # the pressure map is scrapeable at the gateway
            assert "cluster_peer_pressure" in g0.system.metrics.render()

            # heal: pressure gone → admitted again
            victim.governor.remove_signal("hot")
            await victim.system.advertise_status()
            st, _b, _h = await s3.req("PUT", "/pressbkt/after", b"z" * 512)
            assert st == 200

            # STALE gossip must not shed forever: re-pin hot, then age
            # the gateway's status entry past the TTL — a crashed hot
            # node stops blocking its buckets within a few rounds
            from garage_tpu.utils.data import FixedBytes32

            victim.governor.add_signal("hot", lambda: 2.0)
            await victim.system.advertise_status()
            vid = FixedBytes32(bytes(victim.system.id))
            assert g0.system.peer_pressure(vid) >= 1.5
            g0.system._status_at[vid] -= (
                g0.system.PRESSURE_TTL + 1.0)
            assert g0.system.peer_pressure(vid) == 0.0
            st, _b, _h = await s3.req("PUT", "/pressbkt/stale", b"s" * 512)
            assert st == 200
            victim.governor.remove_signal("hot")
    finally:
        await cluster.stop()


# --- config section ----------------------------------------------------


def test_poll_timeout_parse_rejects_poison():
    from garage_tpu.api.common import ApiError
    from garage_tpu.api.k2v_server import parse_poll_timeout

    assert parse_poll_timeout("30") == 30.0
    assert parse_poll_timeout(900) == 600.0          # clamped
    for bad in ("bogus", "nan", "-1", "0", float("nan"), None):
        with pytest.raises(ApiError) as e:
            parse_poll_timeout(bad)
        assert e.value.status == 400                 # typed, not a 500


def test_qos_config_parses_and_validates():
    cfg = config_from_dict({
        "metadata_dir": "/tmp/x", "rpc_secret": "s",
        "api": {"tenant_queue_len": 8, "wdrr_quantum_bytes": "1M",
                "streaming_body_estimate": "64M", "codel_target": 0.25,
                "remote_pressure_shed": 1.2, "retry_after_max": 10},
    })
    assert cfg.api.tenant_queue_len == 8
    assert cfg.api.wdrr_quantum_bytes == 10 ** 6
    assert cfg.api.streaming_body_estimate == 64 * 10 ** 6
    assert cfg.api.codel_target == 0.25
    # a pre-existing config with retry_after above the new cap's default
    # must still boot: the derived ceiling widens instead of raising
    cfg = config_from_dict({"metadata_dir": "/tmp/x", "rpc_secret": "s",
                            "api": {"retry_after": 60}})
    assert cfg.api.retry_after_max == 60
    for bad in ({"tenant_queue_len": 0}, {"codel_interval": 0},
                {"remote_pressure_shed": -1}, {"wdrr_quantum_bytes": 0},
                {"retry_after": 5, "retry_after_max": 2},
                {"max_tracked_tenants": 0}, {"tenant_queue_wait": -1}):
        with pytest.raises(ConfigError):
            config_from_dict({"metadata_dir": "/tmp/x", "rpc_secret": "s",
                              "api": bad})


# --- tenant cardinality bound ------------------------------------------


def test_tenant_tracking_bounded():
    tun = OverloadTunables(max_inflight=0, max_tracked_tenants=4,
                          codel_target=0)
    gate = AdmissionGate(tun)
    toks = [gate.try_admit(tenant=f"t{i}") for i in range(16)]
    # held tenants can't be evicted; the excess shares ~overflow
    assert len(gate._tenants) <= 5
    assert "~overflow" in gate._tenants
    for t in toks:
        t.release()
    assert gate._tenants == {}         # idle tenants are GC'd


def test_probe_cache_updates_on_bucket_recreate():
    from garage_tpu.api.admission import RemotePressureProbe

    probe = RemotePressureProbe(system=None, cache_max=4)
    probe.note_bucket("bkt", b"\x01" * 32)
    probe.note_bucket("bkt", b"\x02" * 32)   # delete + recreate: new id
    assert probe._ids["bkt"] == b"\x02" * 32
    for i in range(8):                       # cache stays bounded
        probe.note_bucket(f"b{i}", bytes([i]) * 32)
    assert len(probe._ids) <= 4


def test_parked_tenant_survives_cardinality_eviction():
    """A tenant whose only request is parked in a long-poll is LIVE:
    the cardinality-cap eviction must not split its accounting."""
    tun = OverloadTunables(max_inflight=0, max_tracked_tenants=2,
                          codel_target=0)
    gate = AdmissionGate(tun)
    poll = gate.try_admit(tenant="poller")
    poll.park()
    te = gate._tenants["poller"]
    assert not te.idle()
    toks = [gate.try_admit(tenant=f"t{i}") for i in range(8)]
    assert gate._tenants.get("poller") is te   # never evicted
    poll.unpark()
    assert te.inflight == 1 and te.parked == 0
    poll.release()
    for t in toks:
        t.release()
    assert gate.inflight == 0 and gate.longpoll_parked == 0


def test_shed_counter_cardinality_bounded():
    """Forged rotating tenant ids must not mint unbounded counter
    series: past the cap, shed attribution collapses into ~overflow."""
    reg = MetricsRegistry()
    tun = OverloadTunables(max_inflight=1, max_tracked_tenants=4,
                          codel_target=0)
    gate = AdmissionGate(tun, metrics=reg)
    hold = gate.try_admit(tenant="legit")
    gate.try_admit(tenant="legit")     # over watermark: sheds from here
    for i in range(64):
        assert gate.try_admit(tenant=f"forged{i}") is None
    labels = {k for k, _v in gate.m_tenant_shed._vals.items()}
    assert len(labels) <= 5            # cap + the one ~overflow bucket
    assert gate.m_tenant_shed.get(tenant="~overflow") > 0
    hold.release()


# --- promlint over every new metric family ------------------------------


async def test_qos_metric_families_pass_promlint():
    from garage_tpu.utils.promlint import lint_exposition

    reg = MetricsRegistry()
    tun = OverloadTunables(max_inflight=2, tenant_queue_wait=0.05,
                          codel_target=0)
    gate = AdmissionGate(tun, metrics=reg)
    gov = LoadGovernor(OverloadTunables(), metrics=reg)
    gate.pressure_fn = gov.pressure
    # exercise every verdict + the queue-wait histogram + parking
    hold = [gate.try_admit(tenant="a"), gate.try_admit(tenant="a")]
    tok, v = await gate.admit(0, tenant="a")
    assert v == "over_share"
    tok, v = await gate.admit(0, tenant="b")
    assert v == "queue_timeout"
    tok, v = await gate.admit(0, tenant="x", remote_pressure=2.0)
    assert v == "remote_pressure"
    hold[0].park()
    body = reg.render()
    for fam in ("api_inflight_requests", "api_admission_total",
                "api_admission_limit", "api_admission_queue_depth",
                "api_admission_queue_wait_seconds", "api_tenant_inflight",
                "api_tenant_shed_total", "api_longpoll_parked"):
        assert fam in body, fam
    assert lint_exposition(body) == []
    hold[0].unpark()
    for t in hold:
        t.release()


def test_fair_share_math():
    tun = OverloadTunables(max_inflight=8, codel_target=0)
    gate = AdmissionGate(tun)
    a = gate.try_admit(tenant="a")
    te_a = gate._tenants["a"]
    assert gate._fair_share(te_a) == math.ceil(8 / 1)
    b = gate.try_admit(tenant="b")
    assert gate._fair_share(te_a) == math.ceil(8 / 2)
    a.release()
    b.release()

"""Hybrid BlockCodec — the CPU floor, and the device behind a link gate.

Why this exists.  The TPU codec's throughput is capped by the
host→device link: on a constrained link the sustained transfer rate can
drop to the same order as — or below — one CPU core's hashing rate, and
it can vary over time (shared tenancy).  Statically routing all codec
work to either backend therefore leaves throughput on the floor.

The hybrid codec owns both sides and decides between them:

  - the CPU codec is the guaranteed floor (hashlib / the native
    multi-buffer BLAKE2s + the native GF kernel);
  - the device codec is built here (on a background thread for the
    daemon, so a node with no accelerator boots on the floor) together
    with the DeviceTransport and the DevicePool over it;
  - the **link gate** measures the host→device round trip
    (`_probe_link`, TTL-cached) and `ragged_side()` reads the cached
    verdict: the device takes work only while the measured rate clears
    `hybrid_min_link_gibs`.

Work reaches the device by ONE road: the CodecFeeder asks
`ragged_side()` and hands open-gate batches to the transport
(ops/feeder.py → ops/transport.py → ops/device_pool.py → the fused
kernels).  The bytes-level calls of the BlockCodec interface that remain
here (`scrub_encode_batch`, `batch_verify`, the `*_ragged` family —
what a caller without a feeder, or the feeder with no transport,
reaches) send the whole batch to the side the gate names (`_routed`),
and a device call that raises is recorded and run again on the floor:
a device failure never fails a codec call.

The reference has no equivalent — its scrub is a strictly sequential
per-block CPU loop (ref src/block/repair.rs:438-490, block.rs:66-78
verify); this is the TPU-first replacement identified in SURVEY.md §7.

Semantics are those of BlockCodec: results are bit-identical whichever
side ran a batch (tests/test_hybrid_codec.py, tests/test_transport.py).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..utils.data import Hash
from .codec import BlockCodec, CodecParams
from .cpu_codec import CpuCodec

logger = logging.getLogger("garage_tpu.ops.hybrid")


class HybridCodec(BlockCodec):
    """CPU floor + the device behind the link gate."""

    def __init__(self, params: CodecParams,
                 device_codec: Optional[BlockCodec] = None,
                 build_device="sync", metrics=None, tracer=None):
        """build_device selects how the device codec is constructed:
          "sync"  — build now (the caller knows the device is there);
          "async" — build on a background thread and attach when ready.
                    This is what the daemon config path uses: JAX backend
                    init can be slow or fail where no device is, and
                    a storage daemon must come up and scrub on its CPU
                    floor regardless (the device joins in when/if init
                    completes);
          False   — never build; pure CPU floor.

        metrics/tracer: the System-owned MetricsRegistry/Tracer — stage
        histograms, bytes-by-side counters, and the gate-decision event
        ring become node-visible (/metrics + admin `codec info`)."""
        super().__init__(params, metrics=metrics, tracer=tracer)
        # the inner CPU codec gets NO observer plumbing: bytes are
        # counted once, where the side is decided (here, the feeder or
        # the transport)
        self.cpu = CpuCodec(params)
        self.tpu = device_codec
        # link-health probe cache (see _probe_link)
        self._link_rate: Optional[float] = None
        self._link_ts = 0.0
        self._link_failed = False
        self._link_ttl = self._LINK_PROBE_TTL_S
        self._fail_ttl = self._LINK_PROBE_FAIL_TTL_S
        self._probe_buf: Optional[np.ndarray] = None
        self._probe_warmed = False
        self._probe_lock = threading.Lock()
        # the zero-copy device transport (ops/transport.py): armed when
        # the device codec speaks the array-level transport API; the
        # CodecFeeder routes device-side ragged batches through it, and
        # the gate probe measures IT instead of the retired
        # serialize+copy path
        self.transport = None
        # the device-resident block pool behind the transport (built by
        # _arm_transport when budgeted); BlockManager's invalidation
        # hooks and the scrub worker's cycle tick reach it here
        self.pool = None
        self._metrics = metrics
        self._governor_ratio = None
        # the gate's last FRESH verdict ("open" | "hold"), the rate it
        # measured and the per-stage breakdown of that probe ({stage:
        # seconds}): what `codec info`, the admin status and the
        # codec_link_gibs gauge print, so a gate-shut node names WHERE
        # the round trip went, not just how slow it was
        self.last_link_gibs: Optional[float] = None
        self.last_gate: Optional[str] = None
        self._link_stages: Optional[dict] = None
        self._stats_lock = threading.Lock()
        self._answered = threading.local()   # see answered_side
        # NOTE: the codec-level gauges (codec_device_attached,
        # codec_link_gibs, codec_tpu_frac) are registered by
        # BlockManager against self.codec — per-instance fn= observers
        # here would pin this instance in the registry forever and go
        # stale on a codec swap (Gauge dedup keeps the FIRST observer).
        if self.tpu is None and build_device:
            if build_device == "async":
                threading.Thread(
                    target=self._build_device_thread,
                    name="codec-hybrid-devinit", daemon=True,
                ).start()
            else:
                self._build_device()
        elif self.tpu is not None:
            self._arm_transport()

    def _arm_transport(self) -> None:
        """Build the DeviceTransport over the attached device codec when
        enabled and the device speaks the array-level transport API
        (scripted test fakes without it are reached by the bytes-level
        calls only)."""
        if not getattr(self.params, "transport", True) or self.tpu is None:
            return
        from .transport import DeviceTransport

        if not DeviceTransport.supports_device(self.tpu):
            return
        # device-resident block pool (ops/device_pool.py): armed when
        # budgeted and the device speaks the pool API; pool_mib=0 or a
        # pool-less device keeps staging byte-identical to the legacy
        # transport
        from .device_pool import DevicePool

        pool = None
        pool_mib = int(getattr(self.params, "pool_mib", 0))
        if pool_mib > 0 and DevicePool.supports_device(self.tpu):
            pool = DevicePool(
                self.tpu,
                pool_bytes=pool_mib << 20,
                page_bytes=int(getattr(self.params, "pool_page_kib",
                                       256)) << 10,
                prefetch=bool(getattr(self.params, "pool_prefetch",
                                      True)),
                metrics=self._metrics, observer=self.obs)
        self.pool = pool
        tr = DeviceTransport(self.tpu, self.params, fallback=self.cpu,
                             observer=self.obs, metrics=self._metrics,
                             pool=pool)
        tr.governor_ratio = self._governor_ratio
        self.transport = tr  # atomic attach (feeder reads it racily)
        self.obs.event("transport_up", reason=type(self.tpu).__name__,
                       slots=tr.slots,
                       pool_mib=pool_mib if pool is not None else 0)

    def set_governor(self, ratio_fn) -> None:
        """Wire the load governor's background_throttle_ratio into the
        transport's background demotion (model/garage.py); survives a
        late async device attach."""
        self._governor_ratio = ratio_fn
        if self.transport is not None:
            self.transport.governor_ratio = ratio_fn

    def _build_device_thread(self) -> None:
        """Async-attach path: the dedicated devinit thread registers
        with the CPU profiler for its lifetime.  The SYNC path calls
        _build_device directly and keeps its caller's role."""
        from ..utils.cpuprof import register_thread, unregister_thread
        register_thread("device-init")
        try:
            self._build_device()
        finally:
            unregister_thread()

    def _build_device(self) -> None:
        try:
            from .tpu_codec import TpuCodec

            # the device codec SHARES this hybrid's observer: kernel
            # demotions land in the same event ring as gate decisions
            self.tpu = TpuCodec(self.params, observer=self.obs)  # atomic attach
            self.obs.event("device_attach", reason="ok")
            self._arm_transport()
        except Exception as e:
            logger.warning(
                "device codec unavailable; hybrid runs CPU-only",
                exc_info=True,
            )
            self.obs.event("device_attach", reason="failed",
                           error=f"{type(e).__name__}: {e}"[:200])

    def info(self) -> dict:
        d = super().info()
        with self._stats_lock:
            d.update({
                "device_attached": self.tpu is not None,
                "device_backend": (type(self.tpu).__name__
                                   if self.tpu is not None else None),
                "gate": self.last_gate,
                "link_gibs": self.last_link_gibs,
                "link_stages": (dict(self._link_stages)
                                if self._link_stages else None),
            })
        if self.transport is not None:
            d["transport"] = self.transport.stats()
        if self.pool is not None:
            d["pool"] = self.pool.stats()
        return d

    def close(self) -> None:
        """Drain the device transport (shutdown path; idempotent)."""
        if self.transport is not None:
            self.transport.shutdown()
        if self.pool is not None:
            self.pool.clear()

    _LINK_PROBE_TTL_S = 15.0
    _LINK_PROBE_FAIL_TTL_S = 2.0
    _LINK_PROBE_TTL_MAX_S = 120.0
    _LINK_PROBE_BYTES = 16 << 20

    def _probe_once(self) -> Tuple[float, bool]:
        """(rate GiB/s, failed?) from one real round-trip.  Transfers a
        16 MiB buffer to the DEVICE CODEC'S device and fetches a scalar
        reduction of it — a device→host fetch of a value that DEPENDS on
        the upload, so the timing covers the whole round trip and not
        the enqueue."""
        try:
            import jax
            import jax.numpy as jnp

            # probe the device codec's OWN device, not jax's default
            # (a codec pinned elsewhere would be mis-measured)
            dev = getattr(self.tpu, "device", None)
            if self._probe_buf is None:
                self._probe_buf = np.random.default_rng(0).integers(
                    0, 256, (self._LINK_PROBE_BYTES,), dtype=np.uint8)

            def roundtrip() -> int:
                buf = (jax.device_put(self._probe_buf, dev)
                       if dev is not None else jnp.asarray(self._probe_buf))
                return int(np.asarray(jnp.sum(buf, dtype=jnp.uint32)))

            if not self._probe_warmed:
                # first call compiles the reduction (seconds on a remote
                # backend) — keep that out of the timed region or a
                # healthy link reads as gated for the whole first TTL
                roundtrip()
                self._probe_warmed = True
            t0 = time.monotonic()
            roundtrip()
            dt = time.monotonic() - t0
            return (self._LINK_PROBE_BYTES / dt / 2**30 if dt > 0 else 0.0,
                    False)
        except Exception:
            logger.warning("device link probe failed", exc_info=True)
            return 0.0, True

    def _probe_link(self) -> float:
        """Measured host→device round-trip rate (GiB/s), cached.

        With a transport armed, the probe measures the NEW path — one
        ragged submission through stage→submit→collect
        (DeviceTransport.probe_link) — not the retired serialize+copy
        round-trip, so the gate decides on the rate the feeder's
        batches will actually see.  A device codec's own `probe_link`
        hook still wins (the synthetic-link backend keeps gate
        decisions deterministic); real codecs are marked `metered_link`;
        anything else (scripted test fakes) is treated as healthy.

        Cache policy: a FAILED probe is retried once immediately and,
        if still failing, re-probed on a doubling ladder
        (_LINK_PROBE_FAIL_TTL_S → _LINK_PROBE_TTL_MAX_S) — a
        durably-dead backend isn't hammered every pass.  A probe that
        SUCCEEDS — even below the gate threshold — caches for exactly
        _LINK_PROBE_TTL_S and resets the failure ladder, so a
        once-failed or once-slow link that recovers is re-probed (and
        the gate re-opened) within one healthy TTL.  The old policy
        doubled the TTL on below-threshold measurements too, which left
        a recovered link gated for up to _LINK_PROBE_TTL_MAX_S.  The
        flat healthy cadence costs nothing when probing is cheap (a
        transport probe or a device hook); only the LEGACY
        _probe_once path — a full 16 MiB round-trip over a possibly
        metered link — keeps the below-threshold backoff ladder.

        A FRESH measurement (never a cached answer) is where the
        verdict is reported: the `probe` and `gate` (open / hold)
        events with the probe's stage breakdown, `last_gate` and
        `last_link_gibs` — so `codec events` answers "why is tpu_frac
        0.0" whoever asked for the probe (the feeder's refresh_gate)."""
        hook = getattr(self.tpu, "probe_link", None)
        hook_owner = self.tpu if hook is not None else None
        tr = self.transport
        if hook is None and tr is not None and tr.alive:
            hook = tr.probe_link
            hook_owner = tr
        legacy = hook is None
        if legacy and not getattr(self.tpu, "metered_link", False):
            return float("inf")
        with self._probe_lock:
            now = time.monotonic()
            if self._link_rate is not None:
                ttl = (self._fail_ttl if self._link_failed
                       else self._link_ttl)
                if now - self._link_ts < ttl:
                    return self._link_rate
            with self.obs.stage("probe", "tpu"):
                if hook is not None:
                    try:
                        rate, failed = (
                            float(hook(self._LINK_PROBE_BYTES)), False)
                        stages = getattr(hook_owner, "last_probe_stages",
                                         None)
                        if stages:
                            self._link_stages = dict(stages)
                    except Exception:
                        logger.warning("probe_link hook failed",
                                       exc_info=True)
                        rate, failed = 0.0, True
                        self._link_stages = None
                else:
                    rate, failed = self._probe_once()
                    if failed:
                        rate, failed = self._probe_once()
            if failed:
                self._fail_ttl = min(self._fail_ttl * 2,
                                     self._LINK_PROBE_TTL_MAX_S)
            elif legacy and rate < self.params.hybrid_min_link_gibs:
                # the probe itself spends metered link quota here:
                # back a below-threshold verdict off as before
                self._fail_ttl = self._LINK_PROBE_FAIL_TTL_S
                self._link_ttl = min(self._link_ttl * 2,
                                     self._LINK_PROBE_TTL_MAX_S)
            else:
                self._fail_ttl = self._LINK_PROBE_FAIL_TTL_S
                self._link_ttl = self._LINK_PROBE_TTL_S
            self._link_failed = failed
            self._link_rate, self._link_ts = rate, now
            self._report_verdict(rate)
            return rate

    def _report_verdict(self, rate: float) -> None:
        """The telemetry of one fresh probe: `probe` and `gate` events
        carrying the stage breakdown, and the two fields `info()` and
        the admin status print."""
        threshold = self.params.hybrid_min_link_gibs
        gate = "open" if rate >= threshold else "hold"
        gibs = round(rate, 4)
        detail = self._stage_detail(self._link_stages)
        with self._stats_lock:
            self.last_link_gibs, self.last_gate = gibs, gate
        self.obs.event("probe", reason="ok", gibs=gibs,
                       threshold=threshold, **detail)
        if gate == "hold":
            self.obs.event("gate", reason="hold", gibs=gibs,
                           threshold=threshold, **detail)
            logger.info(
                "hybrid gate: link probe %.3f GiB/s below threshold "
                "%.3f — the CPU floor runs (dominant stage: %s)",
                rate, threshold, detail.get("dominant_stage", "unknown"))
        else:
            self.obs.event("gate", reason="open", gibs=gibs, **detail)

    def probe_stages(self) -> Optional[dict]:
        """{stage: seconds} of the last successful probe (None when no
        decomposed probe has run — the legacy serialize+copy probe and
        scripted fakes don't stamp stages).  A CACHED verdict reuses the
        breakdown of the measurement that produced it."""
        with self._probe_lock:
            return dict(self._link_stages) if self._link_stages else None

    @staticmethod
    def _stage_detail(stages: Optional[dict]) -> dict:
        """Event-detail kwargs for a probe breakdown: the {stage:
        seconds} map plus its dominant stage (empty when unknown)."""
        if not stages:
            return {}
        from .link_profiler import dominant_stage

        return {"stages": {k: round(v, 6) for k, v in stages.items()},
                "dominant_stage": dominant_stage(stages)}

    # --- the gate's verdict, and the one routing rule ---

    def ragged_side(self) -> str:
        """Which side a batch dispatched NOW runs on: the device only
        when it is attached AND the link probe's CACHED verdict clears
        the gate.  The foreground path must never pay a cold 16 MiB
        probe round-trip — an unprobed or stale link routes to the CPU
        floor and the next background batch (refresh_gate) re-opens
        the gate.  An unmetered backend (no probe_link hook, no
        `metered_link` mark — scripted fakes, local device) is treated
        as healthy, exactly as _probe_link does; that verdict never
        enters the cache, so it is re-derived here rather than read
        from _link_rate."""
        if self.tpu is None:
            return "cpu"
        if self.transport is not None and not self.transport.alive:
            # the transport latched down (repeated device failures or
            # drain): the device path is gone for ragged batches even
            # if the cached link verdict was healthy
            return "cpu"
        if (getattr(self.tpu, "probe_link", None) is None
                and not getattr(self.tpu, "metered_link", False)):
            return "tpu"
        with self._probe_lock:
            rate, ts, failed = self._link_rate, self._link_ts, \
                self._link_failed
        if rate is None:
            return "cpu"
        if rate == float("inf"):
            return "tpu"
        if failed or time.monotonic() - ts > self._LINK_PROBE_TTL_MAX_S:
            return "cpu"
        return ("tpu" if rate >= self.params.hybrid_min_link_gibs
                else "cpu")

    def refresh_gate(self) -> None:
        """Run the (TTL-cached) link probe so the cached gate verdict
        exists/stays fresh.  Called by the feeder before dispatching a
        BACKGROUND batch to a still-closed gate: scrub rides the feeder
        queue and the probe rides with it — background work can afford
        it, foreground never pays it cold."""
        if self.tpu is not None:
            try:
                self._probe_link()
            except Exception:  # noqa: BLE001 — a dead probe = gate stays shut
                logger.warning("gate refresh probe failed", exc_info=True)

    def _routed(self, call: str, *args, nbytes: int = 0,
                probe: bool = False):
        """Run one bytes-level codec call WHOLE on the side the gate
        names, counting `nbytes` there.  `probe`: the call is
        background work with no feeder in front of it (a node with
        `[codec] feeder = false`), so a shut or unprobed gate pays the
        TTL-cached probe here, as the feeder pays it for a background
        batch.  A device call that raises is recorded (`sync_failure`)
        and the batch runs on the CPU floor: the device is never
        allowed to fail a codec call.  (The device codec notes its own
        sync failures into its demotion latch:
        TpuCodec.scrub_encode_batch.)  The side that answered is left
        for the caller that counts the bytes itself (answered_side)."""
        side = self.ragged_side()
        if probe and side == "cpu":
            self.refresh_gate()
            side = self.ragged_side()
        if side == "tpu":
            try:
                out = getattr(self.tpu, call)(*args)
            except Exception as e:  # noqa: BLE001 — degrade to the floor
                logger.warning(
                    "device %s failed; the CPU floor runs the batch: %r",
                    call, e)
                self.obs.event("sync_failure", reason=type(e).__name__,
                               error=f"{e}"[:200])
                side = "cpu"
        if side == "cpu":
            out = getattr(self.cpu, call)(*args)
        self._answered.side = side
        if nbytes:
            # the bytes-level calls that count here are a background
            # caller's verify or fused scrub with no feeder in front
            self.obs.add_bytes(side, nbytes, "scrub")
        return out

    def answered_side(self) -> str:
        """The side that ran this thread's last routed call: what the
        feeder counts an inline batch under (the side it asked for is
        not the side that answered when the device call raised)."""
        return getattr(self._answered, "side", "cpu")

    def _scrub(self, blocks, hashes, fetch_parity, **kw):
        if self.params.rs_data == 0:  # replication-only: verify-only
            return self._routed("batch_verify", blocks, hashes, **kw), None
        return self._routed("scrub_encode_batch", blocks, hashes,
                            fetch_parity, **kw)

    # --- ragged batches (what the CodecFeeder dispatches inline when no
    # transport took the batch; the feeder counts their bytes, under
    # answered_side) ---

    def scrub_ragged(self, items):
        return [self._scrub(b, h, fp) for b, h, fp in items]

    def hash_ragged(self, groups):
        return self._routed("hash_ragged", groups)

    def rs_encode_ragged(self, groups):
        return self._routed("rs_encode_ragged", groups)

    def rs_reconstruct_ragged(self, items):
        return self._routed("rs_reconstruct_ragged", items)

    # --- BlockCodec interface ---

    def batch_hash(self, blocks: Sequence[bytes]) -> List[Hash]:
        # hashing without expectations: no corruption checks to fuse, so
        # the CPU pool is already optimal; wide batches reach the device
        # through the feeder (hash_ragged)
        return self.cpu.batch_hash(blocks)

    def batch_verify(self, blocks: Sequence[bytes], hashes: Sequence[Hash]) -> np.ndarray:
        if len(blocks) != len(hashes):
            raise ValueError(f"{len(blocks)} blocks vs {len(hashes)} hashes")
        if not blocks:
            return np.zeros((0,), dtype=bool)
        return self._routed("batch_verify", blocks, hashes,
                            nbytes=sum(len(b) for b in blocks), probe=True)

    def scrub_encode_batch(self, blocks: Sequence[bytes], hashes: Sequence[Hash],
                           fetch_parity=True):
        """Fused verify + RS(k,m) parity on the side the gate names.

        BlockCodec.scrub_encode_batch's contract: (ok (B,), parity) —
        every row, the rows `fetch_parity` names, or None.  With
        fetch_parity False or empty (or rs_data=0, the replication-only
        config: verify-only) device-side parity stays on the device:
        callers pay device→host bandwidth for the rows they file.
        """
        if not blocks:
            return np.zeros((0,), dtype=bool), None
        return self._scrub(blocks, hashes, fetch_parity,
                           nbytes=sum(len(b) for b in blocks), probe=True)

    def verify_one(self, block: bytes, hash: Hash) -> bool:
        return self.cpu.verify_one(block, hash)

    def rs_encode(self, data: np.ndarray) -> np.ndarray:
        return self.cpu.rs_encode(data)

    def rs_reconstruct(self, shards: np.ndarray, present: Sequence[int],
                       rows: Optional[Sequence[int]] = None) -> np.ndarray:
        return self.cpu.rs_reconstruct(shards, present, rows)

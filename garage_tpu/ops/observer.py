"""CodecObserver — dataplane observability for the BlockCodec layer.

The codec is the system's reason for existing, yet through round 5 it
recorded nothing into the node's MetricsRegistry or Tracer: `tpu_frac`
was a tuple only a bench could read, and a 0.0 value was undiagnosable
(VERDICT r5).  This module gives every codec instance one
observer holding:

  - per-stage duration histograms for the device pipeline
    (`codec_stage_duration_seconds{stage=,side=}`): probe,
    host_staging, device_submit, h2d_transfer, kernel_dispatch,
    sync_collect, feeder_dispatch, transport_wait — the stage-by-stage
    attribution model of the degraded-read / erasure-coding literature
    (arXiv:2306.10528, arXiv:2108.02692);
  - bytes-by-side counters (`codec_bytes_total{side=}`) so tpu_frac is a
    scrapeable ratio, not a bench-polled tuple, and the same bytes by the
    kind of work they were (`codec_work_bytes_total{kind=,side=}`);
  - a bounded, timestamped **gate-decision event ring**: every link
    probe, gate open/hold, fused-kernel demotion, transport error and
    sync failure lands here with a reason label, served by the admin
    `codec events` command — "why is tpu_frac 0.0" is one command.

The ring and the per-stage accumulators are ALWAYS ON (bounded memory,
one lock per event); the Prometheus instruments exist only when a
MetricsRegistry is plumbed in (the daemon path — BlockManager passes
`system.metrics`).  Bare-library users pay one None check.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

# pipeline stages recorded by the hybrid gate, the transport and the
# device codec
STAGES = (
    "probe",           # one fresh link-health probe round-trip (the gate)
    "host_staging",    # batch pad to the compiled lane/byte shape
    "device_submit",   # whole submit envelope (staging+h2d+dispatch)
    "h2d_transfer",    # host→device array transfer (enqueue side)
    "kernel_dispatch", # fused verify+encode dispatch (submit, no sync)
    "sync_collect",    # device→host materialization of a submission
    "feeder_dispatch", # one ragged foreground batch (CodecFeeder) through
                       # hash_ragged / rs_encode_ragged / rs_reconstruct_ragged
    "transport_wait",  # queue wait in the DeviceTransport's EDF heap
                       # (ops/transport.py; its staging/submit/collect
                       # reuse host_staging / device_submit / sync_collect)
)

EVENT_RING_SIZE = 256

# What kind of work the codec's bytes were (`codec_work_bytes_total`):
# scrub (the fused verify + re-encode of the scrub and resync producers,
# and a bytes-level batch_verify), hash (block ids of a PUT, the read
# path's verify), encode (write-time parity), decode (a rebuild from
# parity), mhash (the table engine's Merkle hashing, CPU-only).
BYTE_KINDS = ("scrub", "hash", "encode", "decode", "mhash")


class _StageTimer:
    __slots__ = ("_obs", "_stage", "_side", "_t0")

    def __init__(self, obs: "CodecObserver", stage: str, side: str):
        self._obs = obs
        self._stage = stage
        self._side = side

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._obs.observe_stage(
            self._stage, self._side, time.perf_counter() - self._t0
        )
        return False


class CodecObserver:
    """One per codec instance; shared with the device codec it builds so
    kernel demotions land in the same ring as gate decisions."""

    def __init__(self, metrics=None, tracer=None,
                 ring_size: int = EVENT_RING_SIZE):
        from ..utils.timeline import Timeline

        self.tracer = tracer
        # device/transport timeline: begin/end of every pipeline stage
        # (feeder dispatch, EDF pop, per-slot staging, submit, collect)
        # in one bounded ring, exportable as Chrome-trace JSON (admin
        # `device_timeline`, scripts/device_timeline.py) — the staging
        # overlap is a picture, not an inference
        self.timeline = Timeline()
        # stage-level host<->device attribution (ops/link_profiler.py):
        # the DeviceTransport that shares this observer installs its
        # LinkProfiler here so bench attribution and admin views reach
        # the per-stage breakdown without holding a transport reference
        # across re-arms; None until a transport arms
        self.link_profiler = None
        self.events: deque = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        self._seq = 0
        # always-on accumulators (admin `codec info` + bench attribution
        # read these without a registry): bytes by side, and per-stage
        # (count, seconds) keyed "stage/side"
        self.bytes_total: Dict[str, int] = {"cpu": 0, "tpu": 0}
        self._stage_acc: Dict[str, List[float]] = {}
        if metrics is not None:
            self._hist = metrics.histogram(
                "codec_stage_duration_seconds",
                "Codec pipeline stage durations by stage and side",
            )
            self._bytes_ctr = metrics.counter(
                "codec_bytes_total",
                "Block bytes processed by the codec, by side "
                "(tpu_frac = tpu / (cpu + tpu))",
            )
            self._work_ctr = metrics.counter(
                "codec_work_bytes_total",
                "The bytes of codec_bytes_total by the kind of work they "
                "were (scrub | hash | encode | decode | mhash) and by "
                "side: the kinds of a side sum to codec_bytes_total's",
            )
            self._event_ctr = metrics.counter(
                "codec_gate_events_total",
                "Gate-decision/demotion events by kind and reason",
            )
            self._substage_s = metrics.counter(
                "transport_substage_seconds_total",
                "Seconds inside a transport stage that have a stamp of "
                "their own: compose (pool composition, inside adopt), "
                "pool_adopt (inside collect); the five stages of "
                "transport_stage_seconds are not re-cut",
            )
            self._substage_n = metrics.counter(
                "transport_substage_calls_total",
                "Sections counted in transport_substage_seconds_total "
                "(compose: one a resident dispatch; pool_adopt: one a "
                "collect that adopted)",
            )
            self._pool_programs = metrics.counter(
                "pool_programs_total",
                "Device programs the block pool dispatched, by op "
                "(compose: one a resident dispatch; adopt: one a collect "
                "that adopted; alloc: the page array, once); beside "
                "transport_substage_calls_total this says how many "
                "programs a batch costs",
            )
            self._compile_n = metrics.counter(
                "codec_compiles_total",
                "Device programs JAX built or loaded from its persistent "
                "cache, by the innermost open timeline span of the "
                "compiling thread (`unspanned` where none was open)",
            )
            self._compile_s = metrics.counter(
                "codec_compile_seconds_total",
                "Backend compile seconds of codec_compiles_total, a "
                "cache load's included",
            )
        else:
            self._hist = self._bytes_ctr = self._event_ctr = None
            self._work_ctr = None
            self._substage_s = self._substage_n = None
            self._pool_programs = None
            self._compile_n = self._compile_s = None

    # --- events ---

    def event(self, kind: str, reason: str = "", **detail: Any) -> None:
        """Append one gate-decision event (bounded ring, always on)."""
        with self._lock:
            self._seq += 1
            rec = {"seq": self._seq, "ts": round(time.time(), 3),
                   "kind": kind, "reason": reason}
            if detail:
                rec.update(detail)
            self.events.append(rec)
        if self._event_ctr is not None:
            self._event_ctr.inc(kind=kind, reason=reason)

    def events_list(self, limit: Optional[int] = None) -> List[dict]:
        """Most-recent-last snapshot of the ring."""
        with self._lock:
            out = list(self.events)
        if limit is not None and limit > 0:
            out = out[-limit:]
        return out

    # --- stages ---

    def stage(self, stage: str, side: str) -> _StageTimer:
        return _StageTimer(self, stage, side)

    def observe_stage(self, stage: str, side: str, seconds: float) -> None:
        key = f"{stage}/{side}"
        with self._lock:
            acc = self._stage_acc.get(key)
            if acc is None:
                acc = self._stage_acc[key] = [0, 0.0]
            acc[0] += 1
            acc[1] += seconds
        if self._hist is not None:
            self._hist.observe(seconds, stage=stage, side=side)

    def stage_stats(self) -> Dict[str, dict]:
        """{stage/side: {count, seconds}} — the bench JSON attribution
        block and admin `codec info` both read this."""
        with self._lock:
            return {
                k: {"count": int(c), "seconds": round(s, 6)}
                for k, (c, s) in sorted(self._stage_acc.items())
            }

    def note_substage(self, stage: str, ns: int) -> None:
        """One stamped section inside a transport stage (`compose`,
        `pool_adopt`), counted from the stamps of its timeline span."""
        if self._substage_s is not None:
            self._substage_s.inc(ns / 1e9, stage=stage)
            self._substage_n.inc(stage=stage)

    def note_pool_program(self, op: str) -> None:
        """One device program dispatched by the block pool."""
        if self._pool_programs is not None:
            self._pool_programs.inc(op=op)

    def note_compile(self, where: str, source: str, seconds: float) -> None:
        """One program built (`built`) or loaded from the persistent
        cache (`cache`) under the span `where` (ops/compile_listener.py)."""
        if self._compile_n is not None:
            self._compile_n.inc(**{"where": where, "from": source})
            self._compile_s.inc(seconds, where=where)

    # --- bytes ---

    def add_bytes(self, side: str, n: int, kind: str) -> None:
        """`n` bytes of `kind` work (`BYTE_KINDS`) ran on `side`."""
        with self._lock:
            self.bytes_total[side] = self.bytes_total.get(side, 0) + n
        if self._bytes_ctr is not None:
            self._bytes_ctr.inc(n, side=side)
            self._work_ctr.inc(n, kind=kind, side=side)

    def tpu_frac(self) -> float:
        with self._lock:
            cpu = self.bytes_total.get("cpu", 0)
            tpu = self.bytes_total.get("tpu", 0)
        total = cpu + tpu
        return tpu / total if total else 0.0

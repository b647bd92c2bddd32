"""LinkProfiler — stage-level attribution of every host↔device round
trip (ISSUE 16).

The hybrid gate's one number (`hybrid_link_gibs`: 0.031 GiB/s measured
against a 24 GiB/s device in round 5) says the link is slow but not
WHERE: serialization?  dlpack adoption?  XLA dispatch?  the transfer
itself?  This module is the instrument — the same exact-sum attribution
discipline PR 13 applied to requests, one level down, inside the
transport.

Every profiled round trip is partitioned into the stage taxonomy

    stage_copy  flat-buffer fill (the transport's single host copy)
    adopt       dlpack adoption / device_put of the staged buffer
    compile     dispatch inside which JAX built a program or loaded
                one from its persistent cache (ops/compile_listener.py)
                — split out so cold-start cost never pollutes the
                steady-state dispatch picture
    dispatch    XLA call launch (async; returns before the device runs)
    compute     device busy: submit-return → results ready
                (block_until_ready delta, measured at collect)
    collect     result materialization (D2H) + per-part reassembly

by CONSECUTIVE monotonic boundary stamps, so the per-stage breakdown
sums to the measured round-trip wall time exactly — there is no
unattributed residue to hide movement cost in (the PR 13 waterfall
invariant).  Stamps inside the device come from the device codec's
array API (`last_adopt_ns`, `last_ready_ns`, `last_submit_compiled` —
TpuCodec and SyntheticLinkCodec both publish them); a device that
doesn't stamp degrades gracefully: its time folds into the enclosing
stage instead of vanishing.

Producers: DeviceTransport (every batch + every link probe).
Consumers: `transport_stage_seconds{stage,kind}` histograms, windowed
`transport_stage_gibs{stage}` gauges, admin `codec info` / `codec
profile`, gate probe events and `scripts/link_profile.py`.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

STAGES = ("stage_copy", "adopt", "compile", "dispatch", "compute",
          "collect")

# µs-to-seconds span: a 1 MiB hop on a healthy PCIe link is ~100 µs;
# a degraded link stretches a 16 MiB probe past 500 ms
_STAGE_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.02, 0.05, 0.1,
                  0.5, 2.0, 10.0)


class LinkProfiler:
    """Always-on per-stage accumulator for host↔device round trips.

    `record()` takes one starting stamp plus an ORDERED list of
    (stage, boundary_ns) marks and attributes each inter-mark delta to
    its stage — exactness is structural, not asserted after the fact.
    Accounting is cumulative (count / seconds / bytes per stage, per
    kind); the windowed GiB/s gauge and the sweep harness diff
    snapshots.  The profiler times its own bookkeeping (`overhead_ns`)
    so the <2% overhead bound is measurable, not assumed.
    """

    def __init__(self, metrics=None):
        self._lock = threading.Lock()
        # kind -> stage -> [count, ns, bytes]: integer-ns arithmetic and
        # one dict hop per mark in the hot path (the <2% overhead bound
        # is part of the contract); the per-stage view aggregates lazily
        # at read time
        self._acc: Dict[str, Dict[str, list]] = {}
        self.batches = 0
        self._wall_ns = 0
        self.overhead_ns = 0
        # previous-render (seconds, bytes) snapshot per stage for the
        # windowed GiB/s gauge
        self._win: Dict[str, Tuple[float, int]] = {
            s: (0.0, 0) for s in STAGES}
        if metrics is not None:
            self.m_stage = metrics.histogram(
                "transport_stage_seconds",
                "Host<->device round-trip time by attribution stage "
                "(stage_copy/adopt/compile/dispatch/compute/collect; "
                "stages sum to batch wall time exactly)",
                buckets=_STAGE_BUCKETS)
            metrics.gauge(
                "transport_stage_gibs",
                "Windowed per-stage throughput of the host<->device "
                "path (bytes moved / stage seconds since the previous "
                "render; 0 when the stage was idle)",
                labeled_fn=self._gibs_window)
        else:
            self.m_stage = None

    # --- recording ----------------------------------------------------------

    def record(self, kind: str, nbytes: int, t0_ns: int,
               marks: Sequence[Tuple[str, int]],
               want_breakdown: bool = True) -> Optional[Dict[str, float]]:
        """Attribute one round trip.  `marks` are consecutive boundary
        stamps from `t0_ns`; each delta goes to the named stage, so the
        returned {stage: seconds} breakdown sums to the last mark minus
        `t0_ns` exactly (non-monotonic device stamps are clamped forward
        rather than allowed to create negative or double-counted time).
        `want_breakdown=False` lets the per-batch hot path skip building
        the return dict when no histogram sink needs it either.
        """
        t_in = time.perf_counter_ns()
        build = want_breakdown or self.m_stage is not None
        deltas: Optional[Dict[str, int]] = {} if build else None
        prev = t0_ns
        with self._lock:
            kacc = self._acc.get(kind)
            if kacc is None:
                kacc = self._acc[kind] = {}
            for stage, t in marks:
                dns = t - prev
                if dns > 0:
                    prev = t
                else:
                    dns = 0
                if deltas is not None:
                    if stage in deltas:
                        deltas[stage] += dns
                    else:
                        deltas[stage] = dns
                a = kacc.get(stage)
                if a is None:
                    a = kacc[stage] = [0, 0, 0]
                a[0] += 1
                a[1] += dns
                a[2] += nbytes
            self.batches += 1
            self._wall_ns += prev - t0_ns
        if deltas is None:
            self.overhead_ns += time.perf_counter_ns() - t_in
            return None
        breakdown = {s: dns / 1e9 for s, dns in deltas.items()}
        if self.m_stage is not None:
            for stage, sec in breakdown.items():
                self.m_stage.observe(sec, kind=kind, stage=stage)
        self.overhead_ns += time.perf_counter_ns() - t_in
        return breakdown

    # --- views --------------------------------------------------------------

    def overhead_seconds(self) -> float:
        return self.overhead_ns / 1e9

    @property
    def wall_seconds(self) -> float:
        return self._wall_ns / 1e9

    def _per_stage(self) -> Dict[str, list]:
        """Aggregate (count, ns, bytes) per stage across kinds.
        Callers hold self._lock."""
        out: Dict[str, list] = {}
        for kacc in self._acc.values():
            for stage, (c, ns, b) in kacc.items():
                a = out.get(stage)
                if a is None:
                    a = out[stage] = [0, 0, 0]
                a[0] += c
                a[1] += ns
                a[2] += b
        return out

    def snapshot(self) -> Dict[str, Tuple[int, float, int]]:
        """Immutable (count, seconds, bytes) per stage — the sweep
        harness diffs two of these to attribute one cell."""
        with self._lock:
            per = self._per_stage()
        return {s: (c, ns / 1e9, b) for s, (c, ns, b) in per.items()}

    @staticmethod
    def delta(before: Dict[str, tuple],
              after: Dict[str, tuple]) -> Dict[str, dict]:
        out = {}
        for s, (c1, sec1, b1) in after.items():
            c0, sec0, b0 = before.get(s, (0, 0.0, 0))
            if c1 - c0 or sec1 - sec0:
                out[s] = {"count": c1 - c0,
                          "seconds": round(sec1 - sec0, 9),
                          "bytes": b1 - b0}
        return out

    def summary(self, by_kind: bool = False) -> Dict[str, dict]:
        """The admin/bench view: per-stage count, cumulative seconds,
        bytes and effective GiB/s (bytes/seconds — each stage 'moves'
        the full payload, so a slow stage reads as a slow rate)."""
        with self._lock:
            per = self._per_stage()
            kinds = {k: {s: tuple(a) for s, a in st.items()}
                     for k, st in self._acc.items()} if by_kind else None
        out = {s: {"count": c, "seconds": round(ns / 1e9, 6), "bytes": b,
                   "gibs": round(b / (ns / 1e9) / 2**30, 4)
                   if ns > 0 else None}
               for s, (c, ns, b) in per.items() if c}
        if by_kind and kinds:
            out["by_kind"] = {
                k: {s: {"count": c, "seconds": round(ns / 1e9, 6),
                        "bytes": b}
                    for s, (c, ns, b) in st.items()}
                for k, st in kinds.items()}
        return out

    def _gibs_window(self):
        out = []
        with self._lock:
            per = self._per_stage()
        for s in STAGES:
            _, ns, b = per.get(s, (0, 0, 0))
            sec = ns / 1e9
            psec, pb = self._win.get(s, (0.0, 0))
            self._win[s] = (sec, b)
            ds, db = sec - psec, b - pb
            out.append(({"stage": s}, (db / ds / 2**30) if ds > 0 else 0.0))
        return out

def dominant_stage(stages: Dict[str, float]) -> Optional[str]:
    """The stage owning the most wall time of a breakdown ({stage:
    seconds} or a summary block with 'seconds' entries)."""
    if not stages:
        return None
    best, best_s = None, -1.0
    for k, v in stages.items():
        sec = v.get("seconds", 0.0) if isinstance(v, dict) else float(v)
        if k != "by_kind" and sec > best_s:
            best, best_s = k, sec
    return best


# --- controlled sweep harness (`codec profile` / scripts/link_profile) ------


def _sweep_payload(kind: str, size_bytes: int, blocks: int, k: int,
                   rng) -> tuple:
    """(payload, nblocks, nbytes) for one TransportItem of `kind`:
    `size_bytes` total split into `blocks` equal pieces (encode/scrub
    block counts rounded up to a multiple of rs_data so codeword
    grouping holds; decode ships (B, k, S) survivor shards)."""
    if kind in ("encode", "scrub"):
        blocks += (-blocks) % k
    if kind == "decode":
        ncw = max(1, blocks // k)
        s = max(64, size_bytes // (ncw * k))
        shards = rng.integers(0, 256, (ncw, k, s), dtype=np.uint8)
        present = list(range(k))
        return (shards, present, None), ncw, int(shards.nbytes)
    per = max(64, size_bytes // max(1, blocks))
    buf = rng.integers(0, 256, (blocks * per,), dtype=np.uint8).tobytes()
    blks = [buf[i * per:(i + 1) * per] for i in range(blocks)]
    if kind == "scrub":
        import hashlib

        from ..utils.data import Hash

        hashes = [Hash(hashlib.blake2s(b, digest_size=32).digest())
                  for b in blks]
        return (blks, hashes), blocks, blocks * per
    return blks, blocks, blocks * per


def run_sweep(transport, *, sizes_mib: Sequence[float] = (1, 4, 16, 64),
              shapes: Sequence[int] = (1, 16, 256),
              kinds: Sequence[str] = ("hash", "encode", "decode"),
              rounds: int = 1, warm: bool = True,
              timeout_s: float = 120.0) -> dict:
    """Controlled link sweep: sizes × batch shapes (block counts) ×
    kinds, one cell at a time through the LIVE transport, attributing
    each cell from the profiler's snapshot delta.  Returns the
    machine-readable block (`cells` + per-cell stage breakdowns +
    exact-sum verdicts); `format_sweep()` renders it for humans.

    Serial on purpose: a cell must own the queue while it runs or its
    snapshot delta would blend with foreground traffic.  The admin
    command bounds sizes accordingly — this is a measurement, not a
    load test.  `warm=True` (default) runs one unmeasured round per
    cell first so the measured rounds show the steady-state picture —
    the cold executable's cost lands in the cumulative `compile`
    summary, not in every cell.
    """
    from .transport import TransportItem  # local: transport imports us

    prof = transport.profiler
    k = max(1, transport.params.rs_data)
    rng = np.random.default_rng(16)
    cells: List[dict] = []
    for kind in kinds:
        if not transport.supports(kind):
            continue
        for size_mib in sizes_mib:
            for blocks in shapes:
                payload, nblk, nbytes = _sweep_payload(
                    kind, int(size_mib * 2**20), int(blocks), k, rng)
                stages: Dict[str, float] = {}
                wall = 0.0
                outer = 0.0
                if warm:
                    wi = TransportItem(kind, payload, nblk, nbytes)
                    transport.submit_items(kind, [wi])
                    wi.future.result(timeout=timeout_s)
                for _ in range(max(1, rounds)):
                    item = TransportItem(kind, payload, nblk, nbytes)
                    before = prof.snapshot()
                    w0 = prof.wall_seconds
                    t0 = time.monotonic()
                    transport.submit_items(kind, [item])
                    item.future.result(timeout=timeout_s)
                    outer += time.monotonic() - t0
                    wall += prof.wall_seconds - w0
                    for s, d in prof.delta(before,
                                           prof.snapshot()).items():
                        stages[s] = stages.get(s, 0.0) + d["seconds"]
                stage_sum = sum(stages.values())
                cells.append({
                    "kind": kind,
                    "size_mib": float(size_mib),
                    "blocks": int(blocks),
                    "nbytes": nbytes * max(1, rounds),
                    "wall_s": round(wall, 6),
                    "outer_s": round(outer, 6),
                    "gibs": round(nbytes * max(1, rounds)
                                  / wall / 2**30, 4) if wall > 0 else None,
                    "stages": {s: round(v, 6) for s, v in stages.items()},
                    "dominant": dominant_stage(stages),
                    # exact-sum invariant, live: the breakdown equals the
                    # profiler-measured wall within float rounding, and
                    # never exceeds the caller-observed wall
                    "sum_ok": (abs(stage_sum - wall) < 1e-6
                               and stage_sum <= outer + 1e-6),
                })
    return {
        "sizes_mib": [float(s) for s in sizes_mib],
        "shapes": [int(b) for b in shapes],
        "kinds": list(kinds),
        "rounds": int(rounds),
        "cells": cells,
        "sum_ok": all(c["sum_ok"] for c in cells),
        "overhead_seconds": round(prof.overhead_seconds(), 6),
        "summary": prof.summary(),
    }


def format_sweep(block: dict) -> str:
    """The human attribution table for one `run_sweep` block."""
    from ..utils.format_table import format_table

    rows = ["\t".join(["kind", "MiB", "blocks", "GiB/s",
                       *(f"{s}_ms" for s in STAGES), "dominant", "sum"])]
    for c in block.get("cells", []):
        st = c.get("stages", {})
        rows.append("\t".join([
            c["kind"], f"{c['size_mib']:g}", str(c["blocks"]),
            f"{c['gibs']:.3f}" if c.get("gibs") else "-",
            *(f"{st.get(s, 0.0) * 1e3:.2f}" for s in STAGES),
            c.get("dominant") or "-",
            "ok" if c.get("sum_ok") else "VIOLATED",
        ]))
    return format_table(rows)

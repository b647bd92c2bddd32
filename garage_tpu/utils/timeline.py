"""Timeline — a bounded ring of timestamped events, exportable as
Chrome-trace (catapult) JSON.

The device/transport pipeline's overlap claims (double-buffered staging,
EDF foreground-first dispatch) were only ever *inferred* from counters;
this ring records the actual begin/end of every stage — enqueue, EDF
pop, per-slot staging, device submit, collect — so `chrome://tracing`
(or Perfetto) renders the pipeline as it ran and "did slot 1 stage while
slot 0 computed" is a picture, not an argument.

Always on, bounded (`maxlen` events, each a small dict), one lock.
Producers are the codec feeder, the device transport and the scrub
worker via the shared CodecObserver (`obs.timeline`); consumers are the
admin `device_timeline` command, the HTTP `/v1/timeline` endpoint and
`scripts/device_timeline.py`.

One clock: every stamp is `time.monotonic_ns()`.  `span()` is the one
helper for a synchronous section: it records what `event()` records,
keeps the calling thread's stack of open spans (what the compile
listener, ops/compile_listener.py, puts a compile to) and, where an
`annotate` hook is installed, enters it under `gt:<name>` for the same
interval, so the section is in the profiler's trace too.  The device
codec installs `jax.profiler.TraceAnnotation` as that hook; this module
imports no JAX.  An interval that crosses an `await` is recorded from
its two stamps with `event()`, never held open on the event-loop thread.

Chrome-trace mapping: duration events are phase "X" (ts + dur, µs),
instants are "i", counters are "C".  Tracks ("tid") are stable small
integers assigned per track name, with thread_name metadata events so
the UI shows "slot0", "edf", "feeder" instead of numbers.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

DEFAULT_SIZE = 8192

# per-thread stack of the open spans, innermost last: (name, timeline)
_open = threading.local()


def innermost_span() -> Optional[Tuple[str, "Timeline"]]:
    """(name, timeline) of the calling thread's innermost open span."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


def clock_pair() -> Dict[str, int]:
    """The ring's clock and the wall clock read back to back: the offset
    between this ring's stamps and `tracing.Span` records (`time_ns`)."""
    return {"monotonic_ns": time.monotonic_ns(), "time_ns": time.time_ns()}


class _Span:
    """One open `Timeline.span`; `t0`/`t1` are its stamps, for counters
    that count from the same interval, and `args` may be added to until
    it closes."""

    __slots__ = ("_tl", "_name", "_track", "_cat", "_record", "_mark",
                 "args", "t0", "t1")

    def __init__(self, tl: "Timeline", name: str, track: str, cat: str,
                 record: bool, args: dict):
        self._tl, self._name, self._track = tl, name, track
        self._cat, self._record, self.args = cat, record, args
        self._mark = None
        self.t0 = self.t1 = 0

    def __enter__(self) -> "_Span":
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        stack.append((self._name, self._tl))
        hook = self._tl.annotate
        if hook is not None:
            self._mark = hook(f"gt:{self._name}")
            self._mark.__enter__()
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.monotonic_ns()
        if self._mark is not None:
            self._mark.__exit__(*exc)
        _open.stack.pop()
        if self._record:
            self._tl.event(self._name, self._track, self.t0, self.t1,
                           cat=self._cat, **self.args)
        return False


class Timeline:
    def __init__(self, size: int = DEFAULT_SIZE):
        self._ring: deque = deque(maxlen=size)
        self._lock = threading.Lock()
        self._tracks: Dict[str, int] = {}
        self.dropped = 0  # events evicted by the ring bound
        # `annotate(name, **kw)` -> context manager, entered for every
        # span's interval under `gt:<name>` (the profiler's annotation;
        # None on a node without a device codec: one check per span)
        self.annotate = None

    def span(self, name: str, track: str, cat: str = "transport",
             record: bool = True, **args) -> _Span:
        """A synchronous section [entry, exit] as one duration event.
        `record=False` keeps a per-block section out of the ring: it is
        then on the thread's stack and in the profiler's trace only."""
        return _Span(self, name, track, cat, record, args)

    def mark_clock(self, mono_ns: int) -> None:
        """One instant `gt:clock` annotation carrying `mono_ns`, a stamp
        of this ring's clock taken just before: a reader of the
        profiler's trace gets the offset between its clock and the
        ring's from the annotation's own start."""
        hook = self.annotate
        if hook is not None:
            with hook("gt:clock", mono_ns=mono_ns):
                pass

    def _track(self, name: str) -> int:
        tid = self._tracks.get(name)
        if tid is None:
            tid = self._tracks[name] = len(self._tracks) + 1
        return tid

    def event(self, name: str, track: str, start_ns: int,
              end_ns: Optional[int] = None, cat: str = "transport",
              **args) -> None:
        """Duration event [start_ns, end_ns] (monotonic_ns), or an
        instant when end_ns is None."""
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X" if end_ns is not None else "i",
            "ts": start_ns // 1000,  # chrome wants µs
            "pid": 1,
        }
        if end_ns is not None:
            ev["dur"] = max(0, (end_ns - start_ns) // 1000)
        else:
            ev["s"] = "t"  # instant scope: thread
        if args:
            ev["args"] = args
        with self._lock:
            ev["tid"] = self._track(track)
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(ev)

    def counter(self, name: str, start_ns: int, **values) -> None:
        """Counter sample (stacked area in the trace viewer) — queue
        depths, slot occupancy."""
        ev = {"name": name, "cat": "counter", "ph": "C",
              "ts": start_ns // 1000, "pid": 1,
              "args": {k: float(v) for k, v in values.items()}}
        with self._lock:
            ev["tid"] = self._track("counters")
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(ev)

    def snapshot(self, limit: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._ring)
        if limit is not None and limit > 0:
            out = out[-limit:]
        return out

    def chrome_trace(self, limit: Optional[int] = None) -> dict:
        """The catapult JSON object: sorted traceEvents plus
        process/thread metadata so tracks render with their names."""
        events = sorted(self.snapshot(limit), key=lambda e: e["ts"])
        with self._lock:
            tracks = dict(self._tracks)
        meta: List[dict] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": "garage_tpu device pipeline"},
        }]
        for name, tid in sorted(tracks.items(), key=lambda kv: kv[1]):
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": name}})
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "source": "garage_tpu",
                "captured_at": round(time.time(), 3),
                "dropped_events": self.dropped,
                # monotonic_ns (the clock of every `ts` here) and
                # time_ns (tracing.Span records), read back to back
                **clock_pair(),
            },
        }


def overlapping_slot_windows(chrome: dict) -> int:
    """Count pairs of phase-X events on DISTINCT slot tracks whose time
    windows overlap — the smoke/test assertion that the double buffer
    actually overlapped staging with compute (≥ 1 pair means two slots
    were concurrently occupied)."""
    slots: Dict[int, list] = {}
    names = {}
    for ev in chrome.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[ev["tid"]] = ev["args"]["name"]
    for ev in chrome.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        track = names.get(ev.get("tid"), "")
        if not str(track).startswith("slot"):
            continue
        slots.setdefault(ev["tid"], []).append(
            (ev["ts"], ev["ts"] + ev.get("dur", 0)))
    pairs = 0
    tids = sorted(slots)
    for i, a in enumerate(tids):
        for b in tids[i + 1:]:
            for s0, e0 in slots[a]:
                if any(s0 < e1 and s1 < e0 for s1, e1 in slots[b]):
                    pairs += 1
                    break
    return pairs

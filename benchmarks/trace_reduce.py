"""From a profiler trace to device busy time, op time and idle gaps.

Works on a plain structure, so that a hand-made trace can check it:
`planes = [(plane name, [(line name, [(event name, start_ns, dur_ns)])])]`.
`load_xplane` makes that from the `.xplane.pb` the JAX profiler writes.
"""

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_MARK = "bench:window"
# a v5e's trace buffer ended at 6,290,1xx op events in every run that
# reached it (PERF.md, PR 25); what ran after that is not in the trace
FULL_BUFFER_EVENTS = 6_000_000
HOST_MARK_PREFIX = "bench:"


def load_xplane(trace_dir: str):
    import jax.profiler

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return [(pl.name, [(ln.name, [(short_name(e.name), float(e.start_ns),
                                   float(e.duration_ns))
                                  for e in ln.events])
                       for ln in pl.lines])
            for pl in data.planes]


def short_name(name: str) -> str:
    """An op's own name: the device lines carry the whole HLO
    instruction, `%fusion.3 = (...) fusion(...)`."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def device_planes(planes, prefix=DEVICE_PREFIX):
    return [(n, lines) for n, lines in planes if n.startswith(prefix)]


def op_events(lines):
    """The events of a device plane in which an operation runs: its
    "XLA Ops" line where the trace has one (modules and steps enclose
    their ops and the gaps between them), else every line."""
    named = [evs for name, evs in lines if name == OPS_LINE]
    chosen = named if named else [evs for _n, evs in lines]
    return [e for evs in chosen for e in evs]


def host_marks(planes, prefix=HOST_MARK_PREFIX):
    """[(name, start, end)] of the benchmark's own annotations, from
    every host line."""
    out = []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for _ln, evs in lines:
            out += [(n, s, s + d) for n, s, d in evs if n.startswith(prefix)]
    return out


def window_of(planes):
    """(start, end) ns of the traced window: the `bench:window` mark."""
    marks = [m for m in host_marks(planes) if m[0] == WINDOW_MARK]
    if not marks:
        raise ValueError(f"the trace holds no {WINDOW_MARK} annotation")
    return marks[0][1], marks[0][2]


def reduce_trace(planes, chips: int, window=None, top: int = 10) -> dict:
    """Busy seconds (union of op intervals, mean over the chips used),
    the window's length, per-op device seconds by name, and the longest
    idle gaps by what the host was doing in them."""
    lo, hi = window if window is not None else window_of(planes)
    devs = device_planes(planes)
    full = False
    for _name, lines in devs:
        evs = op_events(lines)
        if len(evs) >= FULL_BUFFER_EVENTS:
            # the buffer filled: the trace is whole only up to its last
            # event, so that is where the window it can speak for ends
            hi = min(hi, max(s + d for _n, s, d in evs))
            full = True
    busy = []
    by_name = {}
    busiest = []
    for _name, lines in devs:
        evs = op_events(lines)
        merged = clip(union((s, s + d) for _n, s, d in evs), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if len(merged) >= len(busiest):
            busiest = merged
        for n, s, d in evs:
            if s + d > lo and s < hi:
                by_name[n] = by_name.get(n, 0.0) + (min(s + d, hi)
                                                    - max(s, lo))
    marks = host_marks(planes)
    gaps = []
    edges = [lo] + [x for s, e in busiest for x in (s, e)] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    by_mark = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        mid = (a + b) / 2
        cover = [m for m in marks if m[1] <= mid < m[2]]
        name = (max(cover, key=lambda m: m[1])[0] if cover
                else "host:unannotated")
        by_mark[name] = by_mark.get(name, 0.0) + (b - a)
    return {
        "busy_s": (sum(busy) / max(chips, 1)) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_planes": len(devs),
        "buffer_full": full,
        "op_seconds": {n: v / 1e9 for n, v in by_name.items()},
        "device_ops": [[n, v / 1e9] for n, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v / 1e9] for n, v in sorted(
            by_mark.items(), key=lambda kv: -kv[1])[:top]],
    }

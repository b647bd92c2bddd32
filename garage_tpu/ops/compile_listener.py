"""Compiles put to the step that caused them.

JAX reports every program it builds, or loads from its persistent
cache, through `jax.monitoring`, on the thread that asked for it.  One
process-wide listener (registered once, however many codecs the process
holds: `jax.monitoring` has no way to take a listener away) reads that
thread's innermost open `Timeline.span` and counts the program under
the span's name into the observer that owns the span's timeline:
`codec_compiles_total{where, from}`, `codec_compile_seconds_total{where}`.
A compile under no span is counted as `unspanned` into every attached
observer: the process compiled, and no step claims it.

The same events give the calling thread a running count, which is what
`TpuCodec.last_submit_compiled` is sourced from: the link profiler's
`compile` stage means that a program was built or loaded inside the
dispatch, not that a (kind, shape) was new to a set.
"""

from __future__ import annotations

import threading
import weakref

from ..utils.timeline import innermost_span

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_observers: "weakref.WeakSet" = weakref.WeakSet()
_thread = threading.local()
registrations = 0   # times the listener pair was handed to jax.monitoring


def attach(observer) -> None:
    """Count compiles into `observer` (a CodecObserver); registers the
    process's one listener pair on the first call."""
    global registrations
    with _lock:
        _observers.add(observer)
        if registrations:
            return
        registrations += 1
    import jax.monitoring as mon

    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)


def thread_compiles() -> int:
    """Programs built or loaded so far on the calling thread."""
    return getattr(_thread, "n", 0)


def _on_event(event: str, **_kw) -> None:
    # a cache hit is reported just before the backend-compile duration
    # of the same program, on the same thread
    if event == CACHE_HIT:
        _thread.hit = True


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event != BACKEND_COMPILE:
        return
    _thread.n = getattr(_thread, "n", 0) + 1
    source = "cache" if getattr(_thread, "hit", False) else "built"
    _thread.hit = False
    span = innermost_span()
    where, timeline = span if span is not None else ("unspanned", None)
    with _lock:
        observers = list(_observers)
    for obs in observers:
        if timeline is None or obs.timeline is timeline:
            obs.note_compile(where, source, seconds)

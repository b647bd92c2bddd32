"""Continuous CPU profiler — always-on thread-stack sampling joined to
the waterfall segment taxonomy.

The scrub headline is CPU-bound with the device idle (ROADMAP
"device-armed round"), and while the PR 13 waterfalls name the dominant
*segment* of a request and the PR 16 link profiler names the dominant
*stage* of a device round trip, nothing could name the *function*
burning the CPU.  This module is that layer:

  - a daemon **sampler thread** walks ``sys._current_frames()`` at a
    configurable rate (default ~29 Hz — deliberately co-prime with
    common 10/25/50/100 ms periodic work so the sampler never phase-
    locks onto a timer loop and over- or under-counts it);
  - every sampled stack is folded into a bounded **stack trie** keyed
    by ``role → segment → frame…``, so memory stays O(max_nodes) no
    matter how long the process runs; when the trie fills, the coldest
    leaves are evicted by folding their counts into their parents
    (total sample counts are conserved — an evicted stack becomes a
    truncated stack, never a lost one);
  - a **thread registry** joins each sample to the attribution layer:
    long-lived threads register a role (``feeder-dispatch``,
    ``transport-stage``, ``incident-write``, …) with a default segment
    from the waterfall taxonomy, and event-loop threads register their
    loop so samples landing on the loop are joined to the *task-local
    span* that was running (``note_span_enter``/``note_span_exit`` are
    called by ``tracing.Span.__enter__/__exit__`` and keep a per-task
    segment stack the sampler can read from a foreign thread — the
    C-accelerated ``asyncio.Task`` on this interpreter exposes no
    ``_context``, so the join is explicit instead of introspective);
  - samples whose leaf frame is a known **waiter** (lock/queue/selector
    waits) count as idle: they feed the per-role busy-ratio window but
    never pollute the flamegraph with parked threads.

Output surfaces: ``cpu_profile_samples_total{role,segment}`` and
windowed ``cpu_busy_ratio{role}`` metrics, collapsed-stack
(flamegraph.pl-compatible) folded lines via ``folded()`` /
``recent_folded()``, and a bounded history ring of per-interval deltas
the flight recorder snapshots into incident bundles.

Everything the sampler does is guarded: a failure to classify one
thread skips that thread, never the sweep; the sweep itself times its
own cost and exports it (``cpu_profiler_overhead_ratio``) so the <2%
overhead budget is measured, not asserted.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
import weakref
from collections import Counter as _Counter
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

from .waterfall import SEGMENTS, segment_of

# --- thread registry -------------------------------------------------------

# Default segment for each registered role.  Roles are a SMALL FIXED
# population (they label metric series); anything unregistered samples
# as role "other".
ROLE_SEGMENTS = {
    "feeder-dispatch": "feeder",
    "feeder-scrub": "codec",
    "hybrid-feeder": "feeder",
    "device-init": "device",
    "transport-stage": "transport",
    "incident-write": "disk",
    "merkle": "codec",
    "codec-hash": "codec",
    "aio-worker": "other",
    "scrub-io": "disk",
    "event-loop": "api",
    "sampler": "other",
    "main": "other",
    "other": "other",
}

_reg_lock = threading.Lock()
_thread_roles: Dict[int, Tuple[str, str]] = {}   # ident -> (role, segment)
_loops: Dict[int, object] = {}                   # ident -> asyncio loop


def register_thread(role: str, segment: Optional[str] = None,
                    ident: Optional[int] = None) -> None:
    """Register the calling thread (or ``ident``) under ``role``.  Call
    from the first line of a long-lived thread's run function; pair
    with :func:`unregister_thread` in its ``finally``."""
    seg = segment or ROLE_SEGMENTS.get(role, "other")
    if seg not in SEGMENTS:
        seg = "other"
    if ident is None:
        ident = threading.get_ident()
    with _reg_lock:
        _thread_roles[ident] = (role, seg)


def unregister_thread(ident: Optional[int] = None) -> None:
    if ident is None:
        ident = threading.get_ident()
    with _reg_lock:
        _thread_roles.pop(ident, None)
        _loops.pop(ident, None)


def register_loop(role: str = "event-loop",
                  loop: Optional[object] = None) -> None:
    """Register the calling thread as an event-loop thread.  Samples on
    it are joined to the running task's span segment instead of the
    role's static default."""
    if loop is None:
        loop = asyncio.get_running_loop()
    ident = threading.get_ident()
    register_thread(role, ident=ident)
    with _reg_lock:
        _loops[ident] = loop


def thread_role(ident: int) -> Tuple[str, str]:
    with _reg_lock:
        rec = _thread_roles.get(ident)
    if rec is not None:
        return rec
    if ident == threading.main_thread().ident:
        return ("main", "other")
    return ("other", "other")


def registered_threads() -> Dict[int, Tuple[str, str]]:
    with _reg_lock:
        return dict(_thread_roles)


# --- task-local span join (the tracing hook) -------------------------------

# task -> stack of active segment names.  Weak keys: a task destroyed
# mid-span (loop torn down) drops its entry with it.  Written only from
# the task's own thread (span enter/exit run inside the task); read by
# the sampler thread under the GIL, guarded.
_task_segments: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_join_enabled = False


def enable_span_join(on: bool = True) -> None:
    """Profilers flip this on at start so un-profiled processes pay
    nothing per span."""
    global _join_enabled
    _join_enabled = on


def note_span_enter(name: str) -> None:
    """Called by ``tracing.Span.__enter__``: record the span's segment
    on the current task's stack so a foreign sampler thread can tag
    event-loop samples with the segment that was actually running."""
    if not _join_enabled:
        return
    try:
        task = asyncio.current_task()
    except RuntimeError:
        return
    if task is None:
        return
    stack = _task_segments.get(task)
    if stack is None:
        stack = []
        _task_segments[task] = stack
    stack.append(segment_of(name))


def note_span_exit() -> None:
    if not _join_enabled:
        return
    try:
        task = asyncio.current_task()
    except RuntimeError:
        return
    if task is None:
        return
    stack = _task_segments.get(task)
    if stack:
        stack.pop()
        if not stack:
            try:
                del _task_segments[task]
            except KeyError:
                pass


def _loop_segment(loop: object) -> Optional[str]:
    """Best-effort: the segment of the span running on ``loop``'s
    current task, read from the sampler thread.  Races with the loop
    are benign under the GIL; any failure means 'unknown'."""
    try:
        task = asyncio.tasks._current_tasks.get(loop)  # noqa: SLF001
        if task is None:
            return None
        stack = _task_segments.get(task)
        if stack:
            return stack[-1]
    except Exception:  # noqa: BLE001 — sampler must never break the node
        return None
    return None


# --- idle classification ---------------------------------------------------

# A sample whose LEAF frame is one of these well-known waiters is a
# parked thread, not CPU work: count it for the busy-ratio denominator
# but keep it out of the flamegraph.
_IDLE_LEAVES = {
    ("threading.py", "wait"),
    ("threading.py", "_wait_for_tstate_lock"),
    ("selectors.py", "select"),
    ("selectors.py", "poll"),
    ("queue.py", "get"),
    ("socket.py", "accept"),
    ("socket.py", "recv"),
    ("socket.py", "recv_into"),
    ("ssl.py", "recv"),
    ("ssl.py", "read"),
    ("subprocess.py", "wait"),
    ("subprocess.py", "_try_wait"),
    ("connection.py", "poll"),
    # a ThreadPoolExecutor worker parked on its (C-implemented)
    # SimpleQueue.get has no Python frame inside the get — the leaf IS
    # _worker, so a whole idle pool would read as busy without this
    ("thread.py", "_worker"),
}


def _is_idle_leaf(frame) -> bool:
    code = frame.f_code
    base = os.path.basename(code.co_filename)
    if (base, code.co_name) not in _IDLE_LEAVES:
        return False
    # GIL-handoff nuance: a foreign sampler acquires the GIL mostly at
    # VOLUNTARY release points, and an event loop with ready callbacks
    # voluntarily releases inside selector.select(timeout=0) every
    # iteration — so a BUSY loop would sample as parked-in-select.  A
    # zero timeout means "poll, there is work queued": that is loop
    # overhead, not idleness.  (Blocking waits pass None or > 0.)
    if base == "selectors.py":
        try:
            if frame.f_locals.get("timeout") == 0:
                return False
        except Exception:  # noqa: BLE001
            return True
    return True


# --- frame labelling -------------------------------------------------------

_label_cache: Dict[int, str] = {}
_LABEL_CACHE_MAX = 8192


def _frame_label(code) -> str:
    """``module.function`` label for one frame, collapsed-stack safe
    (no ``;`` or whitespace).  Memoized per code object."""
    label = _label_cache.get(id(code))
    if label is not None:
        return label
    base = os.path.basename(code.co_filename)
    if base == "__init__.py":
        base = os.path.basename(os.path.dirname(code.co_filename)) or base
    if base.endswith(".py"):
        base = base[:-3]
    label = f"{base}.{code.co_name}".replace(";", ":").replace(" ", "_")
    if len(_label_cache) >= _LABEL_CACHE_MAX:
        _label_cache.clear()
    _label_cache[id(code)] = label
    return label


# --- bounded stack trie ----------------------------------------------------


class _Node:
    __slots__ = ("count", "children")

    def __init__(self):
        self.count = 0          # samples whose stack ENDS here
        self.children: Dict[str, "_Node"] = {}


class StackTrie:
    """Bounded trie of folded stacks.  ``add()`` inserts a root-first
    path; when the node budget is exhausted the path is truncated at
    the deepest existing prefix (counted there, tallied as truncated),
    and the coldest leaves are evicted by folding their counts into
    their parents.  Total counts are conserved across both."""

    def __init__(self, max_nodes: int = 8192):
        self.max_nodes = max(16, int(max_nodes))
        self.root = _Node()
        self.nodes = 0
        self.total = 0
        self.truncated = 0
        self.evicted_nodes = 0

    def add(self, path: Iterable[str], n: int = 1) -> None:
        # evict BEFORE walking: evicting mid-walk could remove the very
        # node the walk is holding and detach the rest of the insertion
        if self.nodes >= self.max_nodes:
            self._evict()
        node = self.root
        for depth, part in enumerate(path):
            child = node.children.get(part)
            if child is None:
                # role/segment nodes (depth 0-1) bypass the budget:
                # they are a small fixed population and truncating them
                # would fold whole roles into the unattributed root
                if depth >= 2 and self.nodes >= self.max_nodes:
                    self.truncated += n
                    break
                child = _Node()
                node.children[part] = child
                self.nodes += 1
            node = child
        node.count += n
        self.total += n

    def _evict(self) -> None:
        """Fold the coldest leaves into their parents until the trie is
        back under 3/4 budget.  A leaf's count moves to its parent —
        the stack gets shorter, the samples stay.  Role/segment nodes
        (depth ≤ 1) are never evicted."""
        target = self.max_nodes * 3 // 4
        while self.nodes > target:
            leaves: List[Tuple[int, _Node, str, _Node]] = []
            stack = [(self.root, None, None, 0)]
            while stack:
                node, parent, key, depth = stack.pop()
                if not node.children and parent is not None and depth > 2:
                    leaves.append((node.count, parent, key, node))
                else:
                    for k, c in node.children.items():
                        stack.append((c, node, k, depth + 1))
            if not leaves:
                break
            leaves.sort(key=lambda rec: rec[0])
            evicted_any = False
            for count, parent, key, _node in leaves[:max(
                    1, self.nodes - target)]:
                if key not in parent.children:
                    continue
                parent.count += count
                del parent.children[key]
                self.nodes -= 1
                self.evicted_nodes += 1
                evicted_any = True
                if self.nodes <= target:
                    break
            if not evicted_any:
                break

    def folded(self) -> _Counter:
        """``{"a;b;c": count}`` for every path with samples."""
        out: _Counter = _Counter()
        stack: List[Tuple[_Node, Tuple[str, ...]]] = [(self.root, ())]
        while stack:
            node, path = stack.pop()
            if node.count and path:
                out[";".join(path)] += node.count
            for k, c in node.children.items():
                stack.append((c, path + (k,)))
        return out


# --- the profiler ----------------------------------------------------------

DEFAULT_HZ = 29.0
MAX_STACK_DEPTH = 48


class CpuProfiler:
    """Always-on sampling profiler.  ``start()`` spawns the daemon
    sampler; tests drive :meth:`sample_once` directly with synthetic
    frames and a fake clock for determinism."""

    def __init__(self, metrics=None, hz: float = DEFAULT_HZ,
                 max_nodes: int = 8192, window_s: float = 60.0,
                 history_s: float = 30.0, flush_s: float = 5.0,
                 clock=time.monotonic):
        self.hz = max(0.1, float(hz))
        self.interval = 1.0 / self.hz
        self.window_s = float(window_s)
        self.history_s = float(history_s)
        self.flush_s = max(0.5, float(flush_s))
        self.clock = clock
        self.trie = StackTrie(max_nodes=max_nodes)
        self.samples = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # per-second busy/total buckets per role, for cpu_busy_ratio
        self._busy: deque = deque()      # (sec, {role: [busy, total]})
        # sampler self-cost buckets, for the overhead ratio
        self._cost: deque = deque()      # (sec, spent_s, wall_s)
        # history ring of folded deltas for the flight recorder
        self._history: deque = deque()   # (t, Counter)
        self._last_fold: _Counter = _Counter()
        self._last_flush = clock()
        self._metrics_samples = None
        if metrics is not None:
            self._register_metrics(metrics)

    # -- metrics --

    def _register_metrics(self, metrics) -> None:
        self._metrics_samples = metrics.counter(
            "cpu_profile_samples_total",
            "CPU profile samples by thread role and joined segment")
        self._metrics_evicted = metrics.counter(
            "cpu_profile_truncated_samples_total",
            "Samples truncated by the stack-trie node budget")
        metrics.gauge(
            "cpu_busy_ratio",
            "Fraction of profiler samples on-CPU per thread role "
            "(rolling window)",
            labeled_fn=lambda: [({"role": r}, v)
                                for r, v in sorted(
                                    self.busy_ratio().items())])
        metrics.gauge("cpu_profiler_overhead_ratio",
                      "Sampler self-cost as a fraction of wall time "
                      "(rolling window)", fn=self.overhead_ratio)
        metrics.gauge("cpu_profile_trie_nodes",
                      "Live nodes in the profiler's bounded stack trie",
                      fn=lambda: float(self.trie.nodes))

    # -- lifecycle --

    def start(self) -> "CpuProfiler":
        if self._thread is not None:
            return self
        enable_span_join(True)
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="cpu-sampler", daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 2.0) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout=timeout)
        self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        register_thread("sampler")
        try:
            while not self._stop.is_set():
                t0 = self.clock()
                try:
                    self.sample_once(now=t0)
                except Exception:  # noqa: BLE001 — never kill the node
                    pass
                spent = self.clock() - t0
                self._note_cost(t0, spent)
                # sleep the REMAINDER of the interval so a slow sweep
                # doesn't compound the sampling rate error
                self._stop.wait(max(0.0, self.interval - spent))
        finally:
            unregister_thread()

    # -- sampling --

    def sample_once(self, now: Optional[float] = None,
                    frames: Optional[Dict[int, object]] = None) -> int:
        """One sweep over every thread's current frame.  ``frames``
        lets tests inject synthetic stacks; production reads
        ``sys._current_frames()``."""
        if now is None:
            now = self.clock()
        if frames is None:
            frames = sys._current_frames()  # noqa: SLF001
        # never sample the sweeping thread itself: it is awake only
        # while sweeping, so it would always observe itself busy
        # (self-observation bias); its true cost is the measured
        # cpu_profiler_overhead_ratio
        self_ident = threading.get_ident()
        with _reg_lock:
            loops = dict(_loops)
        busy_seen = 0
        sec = int(now)
        with self._lock:
            bucket = self._busy[-1][1] if (self._busy
                                           and self._busy[-1][0] == sec) \
                else None
            if bucket is None:
                bucket = {}
                self._busy.append((sec, bucket))
                self._trim(now)
            for ident, frame in frames.items():
                if ident == self_ident:
                    continue
                try:
                    role, seg = thread_role(ident)
                    loop = loops.get(ident)
                    if loop is not None:
                        seg = _loop_segment(loop) or seg
                    idle = _is_idle_leaf(frame)
                    rec = bucket.setdefault(role, [0, 0])
                    rec[1] += 1
                    if idle:
                        continue
                    rec[0] += 1
                    busy_seen += 1
                    path = self._fold_path(role, seg, frame)
                    before_trunc = self.trie.truncated
                    self.trie.add(path)
                    self.samples += 1
                    if self._metrics_samples is not None:
                        self._metrics_samples.inc(
                            1, role=role, segment=seg)
                        if self.trie.truncated != before_trunc:
                            self._metrics_evicted.inc(1)
                except Exception:  # noqa: BLE001 — skip thread, not sweep
                    continue
            if now - self._last_flush >= self.flush_s:
                self._flush_history(now)
        return busy_seen

    @staticmethod
    def _fold_path(role: str, seg: str, frame) -> List[str]:
        rev = []
        f = frame
        while f is not None and len(rev) < MAX_STACK_DEPTH:
            rev.append(_frame_label(f.f_code))
            f = f.f_back
        rev.reverse()            # outermost first (flamegraph order)
        return [role, seg] + rev

    def _note_cost(self, t0: float, spent: float) -> None:
        sec = int(t0)
        with self._lock:
            if self._cost and self._cost[-1][0] == sec:
                last = self._cost[-1]
                self._cost[-1] = (sec, last[1] + spent, last[2])
            else:
                self._cost.append((sec, spent, 1.0))

    def _trim(self, now: float) -> None:
        horizon = now - self.window_s
        while self._busy and self._busy[0][0] < horizon:
            self._busy.popleft()
        while self._cost and self._cost[0][0] < horizon:
            self._cost.popleft()
        hist_horizon = now - self.history_s
        while self._history and self._history[0][0] < hist_horizon:
            self._history.popleft()

    def _flush_history(self, now: float) -> None:
        cur = self.trie.folded()
        delta = cur - self._last_fold
        if delta:
            self._history.append((now, delta))
        self._last_fold = cur
        self._last_flush = now

    # -- readouts --

    def busy_ratio(self) -> Dict[str, float]:
        """Per-role on-CPU fraction over the rolling window."""
        agg: Dict[str, List[int]] = {}
        with self._lock:
            for _sec, bucket in self._busy:
                for role, (busy, total) in bucket.items():
                    rec = agg.setdefault(role, [0, 0])
                    rec[0] += busy
                    rec[1] += total
        return {r: (b / t if t else 0.0) for r, (b, t) in agg.items()}

    def overhead_ratio(self) -> float:
        """Sampler self-cost / wall over the rolling window: the
        measured answer to the <2% overhead budget."""
        with self._lock:
            if not self._cost:
                return 0.0
            spent = sum(s for _sec, s, _n in self._cost)
            wall = max(1.0, self._cost[-1][0] - self._cost[0][0] + 1)
        return spent / wall

    def folded_counter(self) -> _Counter:
        with self._lock:
            return self.trie.folded()

    def folded(self, top_k: Optional[int] = None) -> List[str]:
        """flamegraph.pl-compatible collapsed lines, hottest first."""
        counts = self.folded_counter()
        items = counts.most_common(top_k) if top_k else sorted(
            counts.items(), key=lambda kv: (-kv[1], kv[0]))
        return [f"{stack} {count}" for stack, count in items]

    def recent_folded(self, seconds: float,
                      top_k: Optional[int] = None) -> List[str]:
        """Collapsed lines covering roughly the last ``seconds``:
        flushed history deltas inside the window plus the live
        not-yet-flushed delta.  Served instantly (no re-sampling wait)
        — the sampler is always on."""
        now = self.clock()
        merged: _Counter = _Counter()
        with self._lock:
            for t, delta in self._history:
                if t >= now - seconds:
                    merged.update(delta)
            merged.update(self.trie.folded() - self._last_fold)
        items = merged.most_common(top_k) if top_k else sorted(
            merged.items(), key=lambda kv: (-kv[1], kv[0]))
        return [f"{stack} {count}" for stack, count in items]

    def profile(self, seconds: Optional[float] = 10.0,
                top_k: Optional[int] = 40) -> Dict[str, object]:
        """The machine-readable profile block served by the admin
        command and embedded in incident bundles / BENCH JSON.
        ``seconds=None`` folds the CUMULATIVE trie (everything since
        start — what a bench phase embeds) instead of the bounded
        history window."""
        if seconds is None:
            lines = self.folded(top_k=None)
        else:
            lines = self.recent_folded(seconds, top_k=None)
        total = sum(int(ln.rsplit(" ", 1)[1]) for ln in lines) or 1
        top = []
        for ln in (lines[:top_k] if top_k else lines):
            stack, count = ln.rsplit(" ", 1)
            parts = stack.split(";")
            top.append({
                "stack": stack,
                "role": parts[0] if parts else "other",
                "segment": parts[1] if len(parts) > 1 else "other",
                "leaf": parts[-1] if parts else "",
                "count": int(count),
                "share": round(int(count) / total, 4),
            })
        return {
            "seconds": round(float(seconds), 3) if seconds else None,
            "hz": self.hz,
            "samples": total if lines else 0,
            "busy_ratio": {r: round(v, 4)
                           for r, v in sorted(self.busy_ratio().items())},
            "overhead_ratio": round(self.overhead_ratio(), 5),
            "trie_nodes": self.trie.nodes,
            "truncated_samples": self.trie.truncated,
            "top": top,
        }

    def flight_recorder_section(self, seconds: float = 30.0,
                                top_k: int = 60) -> Dict[str, object]:
        """Collector payload for incident bundles: the last N seconds
        of folded stacks plus the windowed ratios."""
        return self.profile(seconds=seconds, top_k=top_k)


# --- module-level convenience ---------------------------------------------

_default: Optional[CpuProfiler] = None


def install(metrics=None, **kw) -> CpuProfiler:
    """Create/start the process-wide profiler (idempotent)."""
    global _default
    if _default is None:
        _default = CpuProfiler(metrics=metrics, **kw).start()
    return _default


def get() -> Optional[CpuProfiler]:
    return _default


def uninstall() -> None:
    global _default
    if _default is not None:
        _default.stop()
        _default = None
    enable_span_join(False)

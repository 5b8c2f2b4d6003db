"""Span tracing for the telemetry plane (DESIGN.md §10).

Nestable wall-clock spans over the serving path — store epochs, request
groups, kernel dispatches — exported as Chrome trace-event JSON (the
``B``/``E`` duration-event schema) viewable in Perfetto or
``chrome://tracing``.  Spans carry structured tags (store version, epoch
phase, shard, pool shape) in the event ``args``.

Zero-overhead-when-off contract: tracing is OFF by default and ``span()``
then returns a shared no-op context manager after one module-flag check —
no allocation, no clock read, no stack touch.  Enabling tracing never
changes computed values: spans only read clocks and (optionally) block on
already-launched device work so async dispatch time is attributed to the
span that launched it (the *device-sync boundary*, ``sync=``).  The
dispatch-identity tests in tests/test_obs.py hold the stores to that:
pools are leaf-for-leaf identical with tracing on vs off.

Counters ride spans: ``count(name, value)`` adds ``value`` (a host
number or a jax scalar) to a counter of the innermost open span of the
thread.  Jax values are read at span exit, after the span's ``sync``, in
one ``jax.device_get`` per span (``Span.fetch`` folds them into a read the
span makes anyway), and the counters ride the ``E`` event's ``args``.
Off, ``count`` is the same one-flag no-op as ``span``.

While tracing is on, each span also enters a
``jax.profiler.TraceAnnotation`` of its name, so a profiler trace holds
the program's spans on its own clock beside the device's operations; and
``enable()`` hooks two host events for as long as tracing stays on: JAX's
compile-time monitoring events (``jit.<kind>_s`` counters on the innermost
open span) and the garbage collector (a ``host.gc`` span per collection).

Thread model: one event list guarded by a lock, a per-thread span stack.
Timestamps are INTEGER ``perf_counter_ns`` nanoseconds relative to the
tracer's epoch end-to-end (``ts_ns`` on every stored event) — no float
accumulates, so a multi-hour serve trace keeps full sub-µs precision.
The Chrome-facing ``ts`` (µs) is derived at read time by one division;
division by a positive constant is monotone, so ``ts`` never goes
backwards within a thread wherever ``ts_ns`` doesn't.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

# re-entrant: a garbage collection can start while this thread holds the
# lock, and its ``host.gc`` span takes the lock again
_lock = threading.RLock()
_tls = threading.local()

_ON = False
_EVENTS: List[Dict[str, Any]] = []
#: span name -> the counters of each closed span of that name that carried
#: any, for the tracing session that the last ``enable()`` started
_COUNTED: Dict[str, List[Dict[str, Any]]] = {}
_n_counted = 0
_T0_NS = time.perf_counter_ns()
_MAX_EVENTS = 1 << 20          # hard cap: a runaway loop cannot eat the heap

#: JAX monitoring duration events -> the counter each adds to the
#: innermost open span
_JIT_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.jaxpr_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "jit.jaxpr_to_mlir_module_s",
    "/jax/core/compile/backend_compile_duration": "jit.backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "jit.cache_retrieval_s",
}


def enabled() -> bool:
    return _ON


def _on_jit_event(event: str, duration: float, **_) -> None:
    name = _JIT_COUNTERS.get(event)
    if name is not None:
        count(name, duration)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """A ``host.gc`` span per collection, tagged with its generation."""
    if not _ON:
        return
    if phase == "start":
        _tls.gc = Span("host.gc", generation=info["generation"]).__enter__()
    else:
        sp = getattr(_tls, "gc", None)
        _tls.gc = None
        if sp is not None:
            sp.annotate(collected=info.get("collected", 0))
            sp.__exit__(None, None, None)


def enable() -> None:
    """Start a tracing session: timestamps restart at 0, the session's
    ``span_counters`` empty, and the compile and GC hooks are registered."""
    global _ON, _T0_NS, _n_counted
    with _lock:
        _T0_NS = time.perf_counter_ns()
        _COUNTED.clear()
        _n_counted = 0
        if not _ON:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jit_event)
            gc.callbacks.append(_on_gc)
        _ON = True


def disable() -> None:
    """Stop collecting; the hooks are removed, so tracing off costs the
    program nothing."""
    global _ON
    with _lock:
        if _ON:
            jax.monitoring.unregister_event_duration_listener(_on_jit_event)
            gc.callbacks.remove(_on_gc)
        _ON = False


def reset(*, counters: bool = False) -> None:
    """Drop all collected events (enable/disable state unchanged); the
    session's ``span_counters`` stay until the next ``enable()`` unless
    ``counters``."""
    global _n_counted
    with _lock:
        _EVENTS.clear()
        if counters:
            _COUNTED.clear()
            _n_counted = 0


def _now_ns() -> int:
    """The tracer clock: integer nanoseconds since the tracer epoch."""
    return time.perf_counter_ns() - _T0_NS


def _now_us() -> float:
    """Derived µs view of the integer clock (export convenience only —
    nothing stores this)."""
    return _now_ns() / 1e3


def _stack() -> List["Span"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _emit(ev: Dict[str, Any]) -> None:
    with _lock:
        if len(_EVENTS) < _MAX_EVENTS:
            _EVENTS.append(ev)


def _keep(name: str, counters: Dict[str, Any]) -> None:
    global _n_counted
    with _lock:
        if _n_counted < _MAX_EVENTS:
            _n_counted += 1
            _COUNTED.setdefault(name, []).append(counters)


class _NoopSpan:
    """The disabled-path span: a shared singleton, no state, no clock."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **tags):
        return self

    def sync_on(self, tree):
        return self

    def fetch(self, tree):
        return jax.device_get(tree)


_NOOP = _NoopSpan()


class Span:
    """One live span: emits a ``B`` event on enter, ``E`` on exit.

    ``sync`` (optional) is any pytree of jax arrays blocked on at exit so
    asynchronously dispatched device work lands inside this span instead
    of whichever span happens to force the value later (``sync_on`` sets
    it mid-span, once the result exists).
    """
    __slots__ = ("name", "tags", "sync", "_tid", "_counters", "_pending",
                 "_note")

    def __init__(self, name: str, sync=None, **tags):
        self.name = name
        self.tags = tags
        self.sync = sync
        self._counters: Dict[str, Any] = {}
        self._pending: List[tuple] = []

    def annotate(self, **tags) -> "Span":
        """Attach tags discovered mid-span (they ride the ``E`` event)."""
        self.tags.update(tags)
        return self

    def sync_on(self, tree) -> "Span":
        """Block on ``tree`` at exit (the device-sync boundary)."""
        self.sync = tree
        return self

    def _add(self, name: str, value) -> None:
        if isinstance(value, jax.Array):
            self._pending.append((name, value))
        else:
            self._counters[name] = self._counters.get(name, 0) + value

    def _fold(self, values) -> None:
        for (name, _), v in zip(self._pending, values):
            self._counters[name] = self._counters.get(name, 0) + v.item()
        self._pending = []

    def fetch(self, tree):
        """``jax.device_get(tree)``, with the span's pending device
        counters read in the same transfer."""
        host, values = jax.device_get(
            (tree, [v for _, v in self._pending]))
        self._fold(values)
        return host

    def __enter__(self) -> "Span":
        self._tid = threading.get_ident()
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        _stack().append(self)
        _emit({"ph": "B", "name": self.name, "ts_ns": _now_ns(),
               "pid": os.getpid(), "tid": self._tid,
               "args": dict(self.tags) if self.tags else {}})
        return self

    def __exit__(self, *exc) -> bool:
        try:
            if self.sync is not None:
                jax.block_until_ready(self.sync)
            if self._pending:
                self._fold(jax.device_get([v for _, v in self._pending]))
        except Exception:
            self._pending = []     # sync is best-effort attribution only
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:
            st.remove(self)
        args = dict(self.tags) if self.tags else {}
        args.update(self._counters)
        _emit({"ph": "E", "name": self.name, "ts_ns": _now_ns(),
               "pid": os.getpid(), "tid": self._tid, "args": args})
        if self._counters:
            _keep(self.name, self._counters)
        self._note.__exit__(None, None, None)
        return False


def span(name: str, sync=None, **tags):
    """Context manager for one span; the no-op singleton when tracing is
    off (the zero-overhead fast path — one flag check, nothing else)."""
    if not _ON:
        return _NOOP
    return Span(name, sync=sync, **tags)


def count(name: str, value) -> None:
    """Add ``value`` (a host number or a jax scalar) to the counter
    ``name`` of the innermost span open on this thread; a no-op when
    tracing is off or no span is open."""
    if not _ON:
        return
    st = getattr(_tls, "stack", None)
    if st:
        st[-1]._add(name, value)


def span_counters() -> Dict[str, List[Dict[str, Any]]]:
    """Per span name, the counters of each closed span that carried any,
    in closing order, since the last ``enable()`` (``reset()`` keeps
    them: they outlive the event list that a reader drains)."""
    with _lock:
        return {k: [dict(c) for c in v] for k, v in _COUNTED.items()}


def instant(name: str, **tags) -> None:
    """A zero-duration marker event (overflow witness, grow-retry, ...)."""
    if not _ON:
        return
    _emit({"ph": "i", "name": name, "ts_ns": _now_ns(), "pid": os.getpid(),
           "tid": threading.get_ident(), "s": "t",
           "args": dict(tags) if tags else {}})


def events() -> List[Dict[str, Any]]:
    """Collected events with both clocks: the stored integer ``ts_ns``
    and the Chrome-trace ``ts`` (µs) derived from it."""
    with _lock:
        raw = list(_EVENTS)
    return [{**e, "ts": e["ts_ns"] / 1e3} for e in raw]


def export_chrome_trace(path, *, counters: Optional[Dict[str, float]] = None
                        ) -> str:
    """Write the collected spans as Chrome trace-event JSON.

    ``counters`` (name → value, e.g. the metrics registry's kernel
    counters) are appended as ``C`` counter events at the trace tail so
    Perfetto shows them as tracks alongside the spans.
    """
    evs = events()
    if counters:
        ts = evs[-1]["ts"] if evs else _now_us()
        pid = os.getpid()
        for name, value in sorted(counters.items()):
            evs.append({"ph": "C", "name": name, "ts": ts, "pid": pid,
                        "args": {"value": float(value)}})
    payload = {"traceEvents": evs, "displayTimeUnit": "ms"}
    path = str(path)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


__all__ = ["Span", "span", "count", "instant", "enable", "disable",
           "enabled", "reset", "events", "span_counters",
           "export_chrome_trace"]

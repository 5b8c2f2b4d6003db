"""``@timed_dispatch`` — kernel-family entry-point instrumentation.

Wraps the public dispatch wrappers of the three slab kernel families
(``slab_sweep``/``slab_update``/``slab_compact`` ``ops.py``) and records,
per (family, op, pool shape):

* invocation count,
* FIRST-call wall time per shape — dominated by jit compilation — kept
  separate from the steady-state run-time histogram, so compile cost
  never pollutes the latency quantiles.

Neutrality contract (tests/test_obs.py): the wrapper NEVER changes what
the wrapped function computes — enabled, it only times, blocks on the
already-computed result (so async dispatch is attributed correctly), and
counts.  Disabled, the fast path is one flag check and a tail call.

Two guards keep the wrapper composable with the engine architecture:

* a TRACE guard — the sweep entry points are legitimately called inside
  jit/``shard_map``/``lax.while_loop`` bodies (algorithm super-steps);
  under tracing a wall clock is meaningless and ``block_until_ready``
  on tracers would throw, so the wrapper steps aside;
* a REENTRANCY guard — ``sweep_vertices`` calls ``sweep_partials``,
  stacked entry points call per-view bodies; only the OUTERMOST
  instrumented dispatch records, so counters never double-count one
  device program.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional, Tuple

import jax
from jax.core import Tracer

from . import flight, metrics, trace


_PLAIN = frozenset((int, float, bool, str, type(None)))


def _traced(args, kwargs) -> bool:
    """True when any argument leaf is a tracer: the call is being traced
    (jit / shard_map / vmap / while_loop body), not dispatched.  Checked
    per argument, flattening only pytree containers (SlabGraph, tuples of
    views): this runs on every dispatch of the always-on flight path."""
    for a in (args if not kwargs else (*args, *kwargs.values())):
        if type(a) in _PLAIN:
            continue
        if isinstance(a, Tracer):
            return True
        if isinstance(a, jax.Array):
            continue
        if any(isinstance(x, Tracer) for x in jax.tree_util.tree_leaves(a)):
            return True
    return False


_tls = threading.local()
_lock = threading.Lock()

#: (family, op, shape_sig) -> mutable stats record
_KERNEL_STATS: Dict[Tuple[str, str, str], Dict[str, float]] = {}


def _arrays(tree):
    return [x for x in jax.tree_util.tree_leaves(tree)
            if isinstance(x, jax.Array)]


def _shape_sig(args) -> str:
    """Pool-shape signature: the first SlabGraph-ish arg's key-pool shape,
    else the first array leaf's shape — what jit specializes on."""
    for a in args:
        keys = getattr(a, "keys", None)
        if keys is not None and hasattr(keys, "shape"):
            return "x".join(str(d) for d in keys.shape)
        graphs = getattr(a, "graphs", None)   # ShardedSlabGraph
        if graphs is not None and hasattr(graphs, "keys"):
            return "x".join(str(d) for d in graphs.keys.shape)
    arrs = _arrays(args)
    if arrs:
        return "x".join(str(d) for d in arrs[0].shape) or "scalar"
    return "scalar"


def kernel_stats() -> Dict[Tuple[str, str, str], Dict[str, float]]:
    with _lock:
        return {k: dict(v) for k, v in _KERNEL_STATS.items()}


def kernel_summary() -> Dict[str, Dict[str, float]]:
    """JSON-friendly per-(family.op[shape]) record: calls, compile s,
    steady-state s."""
    out = {}
    for (family, op, shape), s in kernel_stats().items():
        out[f"{family}.{op}[{shape}]"] = {
            "family": family, "op": op, "shape": shape,
            "calls": int(s["calls"]),
            "compile_s": s["compile_s"],
            "steady_calls": int(s["steady_calls"]),
            "steady_s": s["steady_s"],
        }
    return out


def reset_kernel_stats() -> None:
    with _lock:
        _KERNEL_STATS.clear()


def _record(family: str, op: str, shape: str, dt_s: float) -> None:
    key = (family, op, shape)
    with _lock:
        s = _KERNEL_STATS.get(key)
        if s is None:
            s = _KERNEL_STATS[key] = {"calls": 0, "compile_s": 0.0,
                                      "steady_calls": 0, "steady_s": 0.0}
        first = s["calls"] == 0
        s["calls"] += 1
        if first:
            # first dispatch per pool shape pays tracing + XLA compilation
            s["compile_s"] = dt_s
        else:
            s["steady_calls"] += 1
            s["steady_s"] += dt_s
    name = f"kernel.{family}.{op}"
    metrics.inc(f"{name}.calls")
    if first:
        metrics.observe(f"{name}.compile", dt_s)
    else:
        metrics.observe(f"{name}.run", dt_s)


def timed_dispatch(family: str, op: Optional[str] = None):
    """Decorator factory for kernel-family entry points (module doc)."""

    def deco(fn):
        op_name = op or fn.__name__
        # interned once per entry point: the flight-recorder hot path is
        # a ring write keyed by this code, no dict lookup per dispatch
        fl_code = flight.intern(f"kernel.{family}.{op_name}")

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not (metrics.enabled() or trace.enabled()
                    or flight.enabled()):
                return fn(*args, **kwargs)
            if getattr(_tls, "depth", 0) > 0 or _traced(args, kwargs):
                return fn(*args, **kwargs)
            if not (metrics.enabled() or trace.enabled()):
                # flight-only (the always-on default): one ring write per
                # outermost dispatch — no shape signature, no block on the
                # result, no timing machinery
                _tls.depth = 1
                try:
                    t0 = time.perf_counter_ns()
                    out = fn(*args, **kwargs)
                    flight.record(fl_code, time.perf_counter_ns() - t0)
                finally:
                    _tls.depth = 0
                return out
            _tls.depth = 1
            try:
                shape = _shape_sig(args)
                t0 = time.perf_counter_ns()
                with trace.span(f"kernel.{family}.{op_name}", shape=shape):
                    out = fn(*args, **kwargs)
                    for a in _arrays(out):
                        a.block_until_ready()
                dt_ns = time.perf_counter_ns() - t0
                flight.record(fl_code, dt_ns)
                _record(family, op_name, shape, dt_ns / 1e9)
            finally:
                _tls.depth = 0
            return out

        wrapper.__wrapped__ = fn
        return wrapper

    return deco


__all__ = ["timed_dispatch", "kernel_stats", "kernel_summary",
           "reset_kernel_stats"]

"""`repro.obs` — the telemetry plane (DESIGN.md §10).

Tracing, metrics, and kernel instrumentation across the stream / sharded /
kernel planes, OFF by default with a one-branch no-op fast path at every
call site.  The paper reports throughput ratios; the serving north star
needs latency SLOs — this package is where p50/p99, per-phase spans and the counts of
work inside the device loops come from, without perturbing the engines' oracle
guarantees (pools are bit-identical with telemetry on or off —
tests/test_obs.py holds both stores to it).

Three modules, one switch:

* ``trace``      — nestable spans (store version / epoch phase / shard /
  pool-shape tags) with counters that ride them (``count``: probe hops
  and visits, hooking rounds, solver iterations, compile seconds), on the
  profiler's clock as ``TraceAnnotation``s, Chrome trace-event JSON
  export for Perfetto;
* ``metrics``    — process-wide counters, gauges, fixed-bucket latency
  histograms with exact p50/p95/p99, structured event stream;
* ``instrument`` — ``@timed_dispatch`` on the kernel families' entry
  points: invocation counts, first-call compile vs steady-state run
  time per pool shape.

``obs.enable()`` arms everything; ``obs.disable()`` restores the no-op
fast path.  ``launch/serve.py --trace out.json / --metrics`` is the
serving surface.
"""
from __future__ import annotations

from . import flight, health, instrument, metrics, postmortem, trace
from .health import HealthEngine, HealthReport, SLOTarget
from .instrument import (kernel_stats, kernel_summary, reset_kernel_stats,
                         timed_dispatch)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, emit_event,
                      get_registry, inc, observe, set_gauge)
from .trace import Span, count, export_chrome_trace, instant, span


def enable(*, tracing: bool = True, metric: bool = True) -> None:
    """Arm the telemetry plane (both sides by default)."""
    if tracing:
        trace.enable()
    if metric:
        metrics.enable()


def disable() -> None:
    """Back to the no-op fast path (collected data is kept until reset).

    The flight recorder (``obs.flight``) is deliberately NOT touched:
    the black box stays on through enable/disable cycles — strip it
    explicitly with ``flight.disable()`` (the neutrality A/B arm)."""
    trace.disable()
    metrics.disable()


def enabled() -> bool:
    return trace.enabled() or metrics.enabled()


def reset() -> None:
    """Drop every collected span and span counter, metric, kernel stat,
    and flight event (the flight ring is emptied but stays armed — see
    ``disable``)."""
    trace.reset(counters=True)
    get_registry().reset()
    reset_kernel_stats()
    flight.reset()


__all__ = [
    "trace", "metrics", "instrument", "flight", "health", "postmortem",
    "enable", "disable", "enabled", "reset",
    "Span", "span", "count", "instant", "export_chrome_trace",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "inc", "observe", "set_gauge", "emit_event",
    "SLOTarget", "HealthEngine", "HealthReport",
    "timed_dispatch", "kernel_stats", "kernel_summary",
    "reset_kernel_stats",
]

"""Statistics of the benchmark: percentiles and open-loop arrival
arithmetic.

``percentile`` is the arithmetic of ``repro.obs.metrics.Histogram`` (an
exact order statistic of the retained samples), copied so that a change to
the program cannot change how the benchmark reads a tail.
"""
from __future__ import annotations

from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile of ``samples``: the sample of rank
    ``round(q / 100 * (n - 1))`` in sorted order."""
    s = sorted(samples)
    if not s:
        raise ValueError("percentile of no samples")
    k = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[k]


def due_times(t0: float, n: int, rate: float) -> list:
    """Open loop: request ``i`` is due at ``t0 + i / rate``, whatever the
    service did before it (``benchmarks/serve_bench.open_loop``)."""
    return [t0 + i / rate for i in range(n)]


def latency_from_due(due: float, done: float) -> float:
    """Open-loop latency: completion minus the time the request was due, so
    a stall also counts against every request queued behind it."""
    return done - due

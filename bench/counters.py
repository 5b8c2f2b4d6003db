"""The program's span counters over a traced run's window.

``repro.obs.trace.count`` adds counters to the innermost open span: probe
hops and visits, hooking rounds, solver iterations, compile seconds.  They
ride the span's end event, and ``obs.trace.span_counters()`` keeps a copy
per span name for the tracing session that the window opened, which
outlives the event list that the harness drains.  A program without
counters reads as None, as does a run without the span.
"""
from __future__ import annotations

from typing import Dict, List, Optional

#: the compile stages that ``jit_ms.*`` sums: tracing to a jaxpr, lowering
#: to MLIR, and the backend compile, which holds any load from the
#: persistent cache (``jit.cache_retrieval_s`` is part of it, not added)
JIT_STAGES = ("jit.jaxpr_trace_s", "jit.jaxpr_to_mlir_module_s",
              "jit.backend_compile_s")


def session() -> Optional[Dict[str, List[dict]]]:
    """Span name -> the counters of each of its spans that carried any, or
    None where the program keeps no counters."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    read = getattr(trace, "span_counters", None)
    return None if read is None else read()


def of(run, name: str) -> Optional[List[dict]]:
    """The counters of each ``name`` span of the window, or None where the
    window has no such span or the program keeps no counters."""
    if not any(s.name == name for s in run.spans):
        return None
    counted = session()
    return None if counted is None else counted.get(name, [])


def probe_useful(run, name: str) -> Optional[float]:
    """100 x the lanes still walking a chain over the lanes gathered, summed
    over every probe loop (``probe.<loop>.{hops,visits,width}``) of the
    ``name`` spans."""
    visits = slots = 0
    for c in of(run, name) or ():
        for key, v in c.items():
            if key.startswith("probe.") and key.endswith(".visits"):
                loop = key[:-len("visits")]
                visits += v
                slots += c[loop + "hops"] * c[loop + "width"]
    return 100.0 * visits / slots if slots else None


def mean(run, name: str, counter: str) -> Optional[float]:
    """The mean of ``counter`` over the ``name`` spans that carry it."""
    values = [c[counter] for c in of(run, name) or () if counter in c]
    return sum(values) / len(values) if values else None


def jit_ms_per_round(run) -> Optional[float]:
    """Milliseconds of compile stages counted in the window, per round
    (0.0 where none fired)."""
    if not run.spans or not run.rounds:
        return None
    counted = session()
    if counted is None:
        return None
    s = sum(c.get(k, 0.0) for rows in counted.values() for c in rows
            for k in JIT_STAGES)
    return 1e3 * s / len(run.rounds)


def mean_ms(run, name: str) -> Optional[float]:
    """The mean duration of the ``name`` spans, in milliseconds."""
    times = [s.ns for s in run.spans if s.name == name]
    return 1e-6 * sum(times) / len(times) if times else None

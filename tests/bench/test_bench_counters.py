"""The readers of the program's span counters: each gives its value on a
synthetic window and None where its spans, or the program's counters, are
absent."""
from __future__ import annotations

import pytest

from benchkit import ROOT


def _window(build):
    """A ``Run`` over the spans and counters that ``build`` makes with the
    program's own tracer, one round long."""
    from repro import obs

    from bench import harness, spans
    obs.reset()
    build(obs)                         # warm-up: nothing compiles after
    obs.trace.enable()
    try:
        build(obs)
    finally:
        obs.trace.disable()
    run = harness.Run(setup_s=1.0, window_s=1.0, rounds=[
        {"t0": 0.0, "t1": 1.0, "edges": 0, "requests": []}] * 2,
        spans=spans.from_events(obs.trace.events()))
    obs.trace.reset()                  # as the harness does: counters stay
    return run


def _probe(obs, loop, hops, visits, width):
    import jax.numpy as jnp
    obs.count(f"probe.{loop}.hops", jnp.int32(hops))
    obs.count(f"probe.{loop}.visits", jnp.int32(visits))
    obs.count(f"probe.{loop}.width", width)


def _ingest(obs):
    for hops, visits in ((4, 100), (2, 28)):
        with obs.span("pipeline.member"):
            with obs.span("store.query"):
                _probe(obs, "query", hops, visits, 64)
    with obs.span("store.apply.dispatch"):
        _probe(obs, "forward.insert", 3, 90, 32)
        _probe(obs, "transpose.insert", 1, 30, 32)
        obs.count("jit.backend_compile_s", 0.25)
        obs.count("jit.cache_retrieval_s", 0.2)   # inside the compile
    with obs.span("pipeline.update"):
        obs.count("jit.jaxpr_trace_s", 0.05)


def _fresh(obs):
    for rounds in (2, 3):
        with obs.span("wcc.refresh"):
            obs.count("wcc.hook_rounds", rounds)
    for iters in (4, 6, 11):
        with obs.span("pagerank.solve"):
            obs.count("pagerank.iters", iters)


def _read(name, run):
    from bench import harness
    return harness.Bench(ROOT).reader(name)(run)


CASES = {
    # 100 x (100 + 28) / (4 x 64 + 2 x 64)
    "query_probe_useful": (_ingest, 100.0 * 128 / 384),
    # 100 x (90 + 30) / (3 x 32 + 1 x 32)
    "update_probe_useful": (_ingest, 100.0 * 120 / 128),
    # (0.25 + 0.05) s over two rounds; the cache load is not added
    "jit_ms.ingest": (_ingest, 150.0),
    "jit_ms.fresh": (_fresh, 0.0),
    "wcc_hook_rounds": (_fresh, 2.5),
    "pagerank_iters": (_fresh, 7.0),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_counter_reader_on_a_synthetic_window(name):
    build, want = CASES[name]
    assert _read(name, _window(build)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["member_query_ms", "wcc_refresh_ms"])
def test_span_time_reader_on_a_synthetic_window(name):
    import time
    span = {"member_query_ms": "store.query",
            "wcc_refresh_ms": "wcc.refresh"}[name]

    def build(obs):
        for _ in range(2):
            with obs.span(span):
                time.sleep(0.01)

    run = _window(build)
    times = [s.ns / 1e6 for s in run.spans if s.name == span]
    assert len(times) == 2
    assert _read(name, run) == pytest.approx(sum(times) / 2)
    assert _read(name, run) >= 10.0


@pytest.mark.parametrize("name", sorted(CASES) + ["member_query_ms",
                                                  "wcc_refresh_ms"])
def test_reader_is_none_where_its_spans_are_absent(name):
    def build(obs):
        with obs.span("pipeline.update"):
            pass

    value = _read(name, _window(build))
    if name.startswith("jit_ms"):
        assert value == 0.0              # the program counts: none fired
    else:
        assert value is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_none_for_a_program_without_counters(name, monkeypatch):
    from repro.obs import trace
    run = _window(CASES[name][0])
    monkeypatch.delattr(trace, "span_counters")
    assert _read(name, run) is None

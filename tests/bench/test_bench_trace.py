"""The reduction from a profiler trace and from ``obs`` spans to the
per-layer numbers."""
from __future__ import annotations

import pathlib
from types import SimpleNamespace as NS

import pytest

FIXTURE = pathlib.Path(__file__).parent / "data" / "tpu_small.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.traced", 100, 1000),
        _ev("bench.update", 100, 500),
        _ev("bench.member", 650, 400),
        _ev("unrelated", 0, 5000)])])
    ops = [_ev("fusion.1", 50, 150), _ev("fusion.1", 300, 100),
           _ev("copy.2", 350, 100), _ev("gather.3", 700, 200)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_x", 0, 2000)]),
        NS(name="XLA Ops", events=ops)])
    return [host, device]


def test_busy_idle_ops_and_gaps_on_a_known_trace():
    from bench.trace_reduce import reduce_planes
    r = reduce_planes(_planes())
    # busy in [100, 1100]: [100,200] + [300,450] + [700,900] = 450 ns
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(450e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_x/fusion.1": 200e-9, "jit_x/copy.2": 100e-9,
         "jit_x/gather.3": 200e-9})
    # idle stretches [200,300] and [450,700] have their midpoints in the
    # update annotation, [900,1100] in the member one
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"update": 350e-9, "member": 200e-9})


def test_trace_without_window_or_device_is_refused():
    from bench.trace_reduce import reduce_planes
    host, device = _planes()
    with pytest.raises(ValueError):
        reduce_planes([device])
    with pytest.raises(ValueError):
        reduce_planes([host])


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: a few jitted calls under the
    benchmark's annotations."""
    from bench.trace_reduce import reduce_file
    r = reduce_file(FIXTURE)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert {k for k, _ in r["idle_gaps"]} <= {"update", "member", "other"}
    times = [s for _, s in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == 10
    assert all(name.startswith("jit__lambda/") for name, _ in
               r["device_ops"])


def test_spans_self_time_and_children():
    from bench.spans import children_seconds, from_events, self_seconds
    ev = [("B", "pipeline.update", 0), ("B", "store.apply", 10),
          ("B", "store.apply.host_dedup", 10), ("E", "store.apply.host_dedup",
                                                30),
          ("B", "store.apply.dispatch", 30), ("E", "store.apply.dispatch", 80),
          ("E", "store.apply", 90), ("B", "store.maintain", 90),
          ("E", "store.maintain", 95), ("E", "pipeline.update", 100),
          ("B", "pipeline.update", 200)]           # never closed: dropped
    spans = from_events([{"ph": p, "name": n, "ts_ns": t, "tid": 1}
                         for p, n, t in ev])
    assert [s.name for s in spans].count("pipeline.update") == 1
    assert self_seconds(spans, "pipeline.update") == [15e-9]
    assert children_seconds(spans, "store.apply",
                            ("store.apply.dispatch",)) == [50e-9]
    assert children_seconds(spans, "store.apply",
                            ("store.apply.host_dedup",
                             "store.apply.capacity")) == [20e-9]

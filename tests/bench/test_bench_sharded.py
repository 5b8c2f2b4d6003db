"""The harness over a sharded store: ``sharded_script.py`` drives a tiny
four-shard cell on four forced CPU host devices in a subprocess (XLA fixes
the device count when it starts), and each scenario it prints is checked
here; the readers of the sharded path's spans, counters and collectives,
and a single store's build, are checked in this process."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchkit import ROOT, run_tiny

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SCRIPT = ROOT / "tests" / "bench" / "sharded_script.py"
FAULTS = ("state_unchanged", "half_batch", "answer_altered",
          "exchange_left_out")


def _names(group, cell="kron-s20.ingest"):
    return {m["name"] for m in SPEC[group]
            if cell in m.get("workloads", [cell])}


@pytest.fixture(scope="module")
def scenarios(tmp_path_factory):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), str(tmp_path_factory.mktemp("s4"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return {ln["scenario"]: ln for ln in lines}


def test_sharded_store_is_built_on_a_mesh_and_reads_as_a_single_one(
        scenarios):
    k = scenarios["keys"]
    assert k["sharded"] and k["on_mesh"] and k["single"]
    assert k["views"] == ["forward", "transpose"]
    assert k["equal"] == {"forward": True, "transpose": True}
    assert k["arcs"] > 0


def test_sharded_cell_is_correct_and_reports_its_metrics(scenarios):
    r = scenarios["run"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert r["count"] == 4
    assert set(r["metrics"]) == _names("end_to_end")
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_traced_sharded_cell_reports_its_per_layer_metrics(scenarios):
    r = scenarios["traced"]
    assert r["correct"]
    assert set(r["metrics"]) == _names("per_layer")
    value = {k: v["value"] for k, v in r["metrics"].items()}
    assert 1.0 <= value["route_skew"] <= 4.0
    assert value["route_ms"] > 0 and value["apply_device_ms"] > 0
    # the stand-in device record: 0.1 s of collectives in 0.5 s busy
    assert value["collective_share.ingest"] == pytest.approx(20.0)


def test_stale_read_control_fails_the_sharded_check(scenarios):
    r = scenarios["run"], scenarios["control"]
    assert r[1]["correct"] and r[1]["control"]["correct"] is False
    ctl = r[1]["control"]["checks"]
    assert ctl["member_mismatch"]["value"] > ctl["member_mismatch"]["limit"]
    assert all(c["value"] <= c["limit"] for k, c in ctl.items()
               if k != "member_mismatch")


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_sharded_path_is_not_correct(scenarios, fault):
    r = scenarios[fault]
    assert not r["correct"]
    assert r["failed"] > 0 or any(c["value"] > c["limit"]
                                  for c in r["checks"].values())


def test_config_without_shards_builds_a_graph_store(tiny_root):
    from bench import harness
    from bench.generator import Graph
    from repro.stream import GraphStore

    bench = harness.Bench(tiny_root)
    config = bench.config("kron-t")
    assert "shards" not in config["store"]
    graph = Graph(config, 3)
    src, dst = graph.edges()
    store = harness.build_store(graph, config, bench.traffic("ingest-t"),
                                src, dst)
    assert type(store) is GraphStore
    with pytest.raises(ValueError):     # four shards, one chip
        harness.build_store(graph, dict(config, store=dict(
            config["store"], shards=4)), bench.traffic("ingest-t"), src, dst,
            [object()])


def test_sharded_config_with_analytics_is_refused(tiny_root):
    from bench import harness
    bench = harness.Bench(tiny_root)
    config = bench.config("urand-t")
    config["store"]["shards"] = 4
    with pytest.raises(ValueError, match="sharded"):
        harness.build_service(None, None, config, bench.traffic("fresh-t"))


def test_single_store_run_keeps_no_counters(tiny_root, monkeypatch):
    from bench import harness
    seen = []
    real = harness.serve_round

    def spy(*args, **kw):
        rec = real(*args, **kw)
        seen.extend(q for q in rec["requests"] if "counters" in q)
        return rec

    device = {"busy_s": 0.5, "window_s": 2.0, "devices": 1,
              "collective_s": 0.0, "device_ops": [], "idle_gaps": []}
    monkeypatch.setattr(harness, "serve_round", spy)
    monkeypatch.setattr(harness.trace_reduce, "reduce_file",
                        lambda path: dict(device))
    r = run_tiny(tiny_root, "kron-t.ingest-t", trace=True)
    assert r["correct"] and not seen
    assert not {"route_ms", "route_skew",
                "collective_share.ingest"} & set(r["metrics"])


# -- the readers -------------------------------------------------------------

def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _device(n, ops):
    return NS(name=f"/device:TPU:{n}", lines=[
        NS(name="XLA Modules", events=[_ev("jit_epoch", 0, 2000)]),
        NS(name="XLA Ops", events=[_ev(*op) for op in ops])])


def test_collective_time_on_two_devices():
    from bench.trace_reduce import reduce_planes
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.traced", 100, 1000), _ev("bench.update", 100, 1000)])])
    dev0 = _device(0, [("fusion.1", 100, 200), ("all-to-all.3", 300, 100),
                       ("all-to-all.4", 350, 100),    # overlaps: 150 ns
                       ("all-reduce-start.1", 1050, 200)])  # 50 ns inside
    dev1 = _device(1, [("collective-permute-done.2", 0, 150),  # 50 inside
                       ("fusion.1", 400, 300)])
    r = reduce_planes([host, dev0, dev1])
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx((200 + 50) / 2 * 1e-9)
    assert r["busy_s"] == pytest.approx((200 + 150 + 50 + 50 + 300) / 2
                                        * 1e-9)
    one = reduce_planes([host, _device(0, [("fusion.1", 100, 200)])])
    assert one["collective_s"] == 0.0


def _run(spans=(), counters=(), device=None):
    from bench import harness
    updates = [{"kind": "update", "name": "update", "seconds": 0.1,
                "counters": c} for c in counters]
    return harness.Run(setup_s=1.0, window_s=1.0, spans=list(spans),
                       device=device, rounds=[
                           {"t0": 0.0, "t1": 1.0, "edges": 0,
                            "requests": updates}])


def _read(name, run):
    from bench import harness
    return harness.Bench(ROOT).reader(name)(run)


def test_route_ms_reads_the_route_span_per_epoch():
    from bench.spans import Span
    spans = [Span(0, "store.apply", 0, 10_000_000, None),
             Span(1, "store.apply.route", 1_000_000, 3_000_000, 0),
             Span(2, "store.apply", 20_000_000, 30_000_000, None),
             Span(3, "store.apply.route", 21_000_000, 25_000_000, 2)]
    assert _read("route_ms", _run(spans)) == pytest.approx(3.0)
    assert _read("route_ms", _run(spans[:1] + spans[2:3])) == 0.0
    assert _read("route_ms", _run()) is None


def test_route_skew_from_the_per_shard_counters():
    def snap(ins, dels):
        out = {f"store.route.ins.shard{k}": v for k, v in enumerate(ins)}
        if dels:
            out.update({f"store.route.del.shard{k}": v
                        for k, v in enumerate(dels)})
        out["store.apply.epochs"] = 1
        return out

    # epoch 1 inserts 4/2/1/1, epoch 2 deletes 2/2/2/2 (the insert
    # counters stand still), epoch 3 inserts 0/8/0/0
    counters = [snap((4, 2, 1, 1), None), snap((4, 2, 1, 1), (2, 2, 2, 2)),
                snap((4, 10, 1, 1), (2, 2, 2, 2))]
    assert _read("route_skew", _run(counters=counters)) == pytest.approx(
        4 * (4 + 2 + 8) / (8 + 8 + 8))
    even = [snap((2, 2, 2, 2), None)]
    assert _read("route_skew", _run(counters=even)) == pytest.approx(1.0)
    assert _read("route_skew", _run()) is None


def test_collective_share_of_busy_time():
    dev = {"busy_s": 2.0, "window_s": 3.0, "devices": 4,
           "collective_s": 0.5}
    assert _read("collective_share.ingest", _run(device=dev)) == \
        pytest.approx(25.0)
    assert _read("collective_share.ingest",
                 _run(device=dict(dev, devices=1))) is None
    assert _read("collective_share.ingest", _run()) is None

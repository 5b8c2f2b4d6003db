"""Whole runs of tiny cells on the CPU, through the harness's functions:
correct answers check as correct, each cell reports its metrics."""
from __future__ import annotations

import json

import pytest

from benchkit import ROOT, TINY_CELLS, run_tiny

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _names(group, like):
    return {m["name"] for m in SPEC[group]
            if like in m.get("workloads", [like])}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_tiny_cell_is_correct_and_reports_its_metrics(tiny_root, cell):
    r = run_tiny(tiny_root, cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == _names("end_to_end", TINY_CELLS[cell])
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert r["device"]["count"] == 1


@pytest.mark.parametrize("cell", ["kron-t.ingest-t", "urand-t.fresh-t"])
def test_traced_run_reports_the_per_layer_metrics(tiny_root, cell,
                                                  monkeypatch):
    # the CPU has no TPU plane to reduce: stand a device record in for it
    from bench import harness
    device = {"busy_s": 0.5, "window_s": 2.0, "devices": 1,
              "device_ops": [["fusion", 0.5]], "idle_gaps": [["update", 1.5]]}
    monkeypatch.setattr(harness.trace_reduce, "reduce_file",
                        lambda path: dict(device))
    r = run_tiny(tiny_root, cell, trace=True)
    assert r["correct"]
    assert set(r["metrics"]) == _names("per_layer", TINY_CELLS[cell])
    idle = [k for k in r["metrics"] if k.startswith("idle_share")]
    assert [r["metrics"][k]["value"] for k in idle] == [75.0]
    assert r["device"]["busy_s"] == 0.5 and r["breakdown"]["device_ops"]

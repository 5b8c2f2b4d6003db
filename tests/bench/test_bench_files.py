"""The benchmark's files: BENCHMARK.json keeps to its contract, and every
configuration, traffic mix and metric is a file found by its name."""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from benchkit import ROOT, add_cell, add_config, run_tiny

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert SPEC["command"][:2] == ["python3", "-m"]
    assert (ROOT / (SPEC["command"][2].replace(".", "/") + ".py")).is_file()
    assert all((ROOT / p).is_dir() and ".." not in p for p in SPEC["paths"])
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lengths():
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]


def test_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    cells = [w["name"] for w in SPEC["workloads"]]
    for cell in cells:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if cell in m.get("workloads", [cell])]
        layers = [m for m in SPEC["per_layer"]
                  if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layers
        for m in layers:                  # each moves a metric it reports
            assert m["moves"] in e2e
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(cells) // 2)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_found_by_name_and_states_its_cut(entry):
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key in ("source", "assumed", "guarantees", "store", "generator"):
        assert key in config
    assert "visibility" in config["guarantees"]


@pytest.mark.parametrize("traffic", sorted({w["traffic"]
                                            for w in SPEC["workloads"]}))
def test_traffic_found_by_name(traffic):
    from bench import harness
    mix = harness.Bench(ROOT).traffic(traffic)
    assert {s["kind"] for s in mix["round"]} <= {"update", "member",
                                                 "property"}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["end_to_end"]
                                    + SPEC["per_layer"]])
def test_metric_reader_found_by_name(metric):
    from bench import harness
    read = harness.Bench(ROOT).reader(metric)
    empty = harness.Run(setup_s=1.0, window_s=1.0,
                        rounds=[{"t0": 0.0, "t1": 1.0, "edges": 0,
                                 "requests": []}])
    value = read(empty)
    assert value is None or metric == "setup_s"


def test_peaks_table_keyed_by_device_kind():
    from bench import harness
    bench = harness.Bench(ROOT)
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench.peaks("cpu")


def test_dummy_configuration_runs_with_no_other_file_edited(tmp_path):
    """A configuration and a mix dropped into a copy of the benchmark, with
    their cell listed, run: nothing else is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    add_config(tmp_path, "dummy", {
        "name": "dummy", "source": "test", "generator": "uniform",
        "scale": 9, "edgefactor": 8, "directed": True, "reduced": {},
        "assumed": {},
        "guarantees": {}, "analytics": {"pagerank": {"damping": 0.85}},
        "store": {"hashing": False, "with_symmetric": False,
                  "tombstone_ratio": 0.2, "policy": "lazy"}})
    (tmp_path / "bench" / "traffic" / "poke.json").write_text(json.dumps(
        {"round": [{"kind": "update", "deletes": 64, "inserts": 64},
                   {"kind": "member", "pairs": 64,
                    "mix": {"live": 0.5, "random": 0.5}}]}))
    cell = add_cell(tmp_path, "dummy", "poke", "kron-s21.ingest")
    for p, data in before.items():
        assert p.read_bytes() == data
    result = run_tiny(tmp_path, cell, seconds=0.3)
    assert result["correct"] and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in SPEC["end_to_end"]
        if "kron-s21.ingest" in m.get("workloads", ["kron-s21.ingest"])}


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "kron-s21.ingest",
         "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_tpu_exits_2_and_prints_no_result():
    proc = _cli(ROOT)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


def test_cli_without_the_program_fails(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _cli(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""

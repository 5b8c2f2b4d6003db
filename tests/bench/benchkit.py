"""A temporary copy of the benchmark with tiny cells, run on the CPU through
the harness's own functions."""
from __future__ import annotations

import json
import pathlib
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: tiny configuration -> (the real configuration it copies, its scale)
TINY_CONFIGS = {"kron-t": ("kron-s21", 10), "urand-t": ("urand-s18", 10)}
TINY_TRAFFIC = {
    "ingest-t": {"round": [
        {"kind": "update", "deletes": 0, "inserts": 256},
        {"kind": "member", "pairs": 256, "repeat": 2,
         "mix": {"just_inserted": 0.25, "live": 0.5, "random": 0.25}},
        {"kind": "update", "deletes": 256, "inserts": 0},
        {"kind": "member", "pairs": 256, "repeat": 2,
         "mix": {"just_deleted": 0.25, "live": 0.5, "random": 0.25}}]},
    "fresh-t": {"round": [
        {"kind": "update", "deletes": 0, "inserts": 128},
        {"kind": "property", "name": "pagerank"},
        {"kind": "property", "name": "wcc"},
        {"kind": "update", "deletes": 128, "inserts": 0},
        {"kind": "property", "name": "pagerank"},
        {"kind": "property", "name": "wcc"}]},
}
#: tiny cell -> the real cell whose metrics it reports
TINY_CELLS = {"kron-t.ingest-t": "kron-s21.ingest",
              "urand-t.fresh-t": "urand-s18.fresh",
              "urand-t.ingest-t": "kron-s21.ingest"}


def add_config(root: pathlib.Path, name: str, config: dict) -> None:
    """Drop ``config`` into ``bench/configs`` and list it in
    BENCHMARK.json."""
    path = root / "bench" / "configs" / f"{name}.json"
    path.write_text(json.dumps(config))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "test",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def add_cell(root: pathlib.Path, config: str, traffic: str, like: str,
             chips: int = 1):
    """List the cell ``config.traffic`` with the metrics of the cell
    ``like``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": chips,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return name


def make_root(dest: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark with the tiny configurations, mixes and
    cells added as data files and entries."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for name, (real, scale) in TINY_CONFIGS.items():
        config = json.loads(
            (ROOT / "bench" / "configs" / f"{real}.json").read_text())
        config.update(name=name, scale=scale)
        add_config(dest, name, config)
    for name, traffic in TINY_TRAFFIC.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
    for cell, like in TINY_CELLS.items():
        add_cell(dest, *cell.split("."), like)
    return dest


def run_tiny(root, workload: str, *, trace: bool = False,
             seconds: float = 0.5, seed: int = 2 ** 33 + 17, control=None,
             chips: int = 1):
    import jax

    from bench import harness
    return harness.run_cell(
        harness.Bench(root), workload, seed, seconds, trace,
        devices=jax.devices()[:chips], t_start=time.perf_counter(),
        log=lambda msg: None, control=control)

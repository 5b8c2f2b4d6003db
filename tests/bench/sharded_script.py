"""Drives the harness over a tiny four-shard copy of ``kron-s20.ingest`` on
four forced CPU host devices, and prints one JSON line per scenario.

XLA fixes the number of host devices when it starts, so
``test_bench_sharded.py`` runs this in a subprocess:

    python3 tests/bench/sharded_script.py <empty directory>

Scenarios, in order: ``keys`` (the sharded store's key lists against a
single store's on the same edges), ``run`` (an untraced run), ``traced``
(a traced run, with a stand-in device record), ``control`` (the stale-read
control), then the timed path broken underneath: ``state_unchanged``,
``half_batch``, ``answer_altered`` and, last because it recompiles the
epoch, ``exchange_left_out``.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import pathlib  # noqa: E402
from unittest import mock  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchkit import add_cell, add_config, make_root, run_tiny  # noqa: E402

CELL = "kron-t4.ingest-t"
#: what the stand-in device record of the traced run says
DEVICE = {"busy_s": 0.5, "window_s": 2.0, "devices": 4, "collective_s": 0.1,
          "device_ops": [["fusion", 0.5]], "idle_gaps": [["update", 1.5]]}


def tiny_sharded_root(dest: pathlib.Path) -> pathlib.Path:
    root = make_root(dest)
    config = json.loads(
        (ROOT / "bench" / "configs" / "kron-s20.json").read_text())
    config.update(name="kron-t4", scale=10)
    add_config(root, "kron-t4", config)
    add_cell(root, "kron-t4", "ingest-t", "kron-s20.ingest", chips=4)
    return root


def keys(root):
    from bench import harness
    from bench.generator import Graph
    from repro.stream import GraphStore, ShardedGraphStore

    bench = harness.Bench(root)
    config, traffic = bench.config("kron-t4"), bench.traffic("ingest-t")
    graph = Graph(config, 5)
    src, dst = graph.edges()
    sharded = harness.build_store(graph, config, traffic, src, dst,
                                  jax.devices()[:4])
    single = harness.build_store(
        graph, dict(config, store=dict(config["store"], shards=1)), traffic,
        src, dst)
    return {"sharded": isinstance(sharded, ShardedGraphStore),
            "on_mesh": sharded.mesh is not None
            and sharded.mesh.devices.size == 4,
            "single": isinstance(single, GraphStore),
            "views": sorted(sharded.views),
            "equal": {v: bool(np.array_equal(
                harness._store_keys(sharded, v, v == "transpose"),
                harness._store_keys(single, v, v == "transpose")))
                for v in sharded.views},
            "arcs": int(len(harness._store_keys(sharded, "forward",
                                                False)))}


def _state_unchanged():
    """The epoch computes its answer but hands back the views it was
    given."""
    from repro.stream import sharded_store
    real = sharded_store._apply_sm_don

    def unchanged(views, *args, **kw):
        copies = jax.tree.map(jnp.copy, views)
        _, *rest = real(copies, *args, **kw)
        return (views, *rest)

    return mock.patch.object(sharded_store, "_apply_sm_don", unchanged)


def _half_batch():
    """Half of each update batch is left out."""
    from repro.stream import ShardedGraphStore
    real = ShardedGraphStore.apply

    def half(self, ins_src=None, ins_dst=None, ins_w=None, del_src=None,
             del_dst=None):
        cut = [None if a is None else np.asarray(a)[:len(a) // 2]
               for a in (ins_src, ins_dst, del_src, del_dst)]
        return real(self, cut[0], cut[1], ins_w, cut[2], cut[3])

    return mock.patch.object(ShardedGraphStore, "apply", half)


def _answer_altered():
    """One membership answer flipped where it is produced."""
    from repro.stream import ShardedGraphStore
    real = ShardedGraphStore.query

    def query(self, src, dst):
        found = real(self, src, dst).copy()
        found[0] = ~found[0]
        return found

    return mock.patch.object(ShardedGraphStore, "query", query)


def _exchange_left_out():
    """Each shard keeps the buckets it routed instead of exchanging them
    with the owner shards: no all-to-all."""
    from repro.distributed import sharded_graph
    jax.clear_caches()              # the real exchange is compiled in
    return mock.patch.object(sharded_graph, "exchange_buckets",
                             lambda buckets, axis_name="shard": buckets)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "exchange_left_out": _exchange_left_out}


def _summary(r):
    return {"correct": r["correct"], "failed": r["failed"],
            "attempted": r["attempted"], "metrics": r["metrics"],
            "count": r["device"]["count"], "checks": r["checks"],
            "control": r.get("control")}


def main(dest):
    from bench import control, harness

    assert len(jax.devices()) == 4, jax.devices()
    root = tiny_sharded_root(pathlib.Path(dest))

    def emit(name, result):
        print(json.dumps({"scenario": name, **result}), flush=True)

    emit("keys", keys(root))
    emit("run", _summary(run_tiny(root, CELL, chips=4)))
    with mock.patch.object(harness.trace_reduce, "reduce_file",
                           lambda path: dict(DEVICE)):
        emit("traced", _summary(run_tiny(root, CELL, chips=4, trace=True)))
    emit("control", _summary(run_tiny(root, CELL, chips=4,
                                      control=control.answers)))
    for name, fault in FAULTS.items():
        with fault():
            emit(name, _summary(run_tiny(root, CELL, chips=4)))


if __name__ == "__main__":
    main(sys.argv[1])

"""Time, one by one, the program's set-up steps that a benchmark run pays
inside ``GraphStore.from_edges`` and the analytics' init, for one cell and
seed: the repeated dedup, the host pool build of each view, the copy of the
pools to the device, and each analytic's first solve.

    python3 -m bench.setup_costs --workload kron-s21.ingest --seed 7

Each line is ``<step> <seconds>``; a step that the cell's served path does
not take is not timed.  It needs a TPU.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(run.ROOT / ".bench_cache" / "jax"))
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    from bench import harness
    from bench.generator import Graph, arcs
    from repro.core.slab_graph import from_edges_numpy
    from repro.stream import dedup_pairs

    bench = harness.Bench(run.ROOT)
    cell = bench.cell(args.workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(
        cell["traffic"])
    graph = Graph(config, args.seed)
    edges = graph.edges()
    src, dst = arcs(*edges, graph.directed)

    def timed(step, fn):
        t = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        print(f"{step} {time.perf_counter() - t:.3f}", flush=True)
        return out

    src, dst, _ = timed("from_edges.dedup_pairs",
                        lambda: dedup_pairs(src, dst))
    inserts = graph.arcs_per_edge * harness._most(traffic, "inserts")
    kw = dict(hashing=config["store"]["hashing"],
              slack_slabs=inserts // 64 + 512)
    views = [timed(f"from_edges.pool_build.{name}",
                   lambda a=a, b=b: from_edges_numpy(graph.n_vertices, a, b,
                                                     **kw))
             for name, a, b in (("forward", src, dst),
                                ("transpose", dst, src))]
    timed("from_edges.device_put",
          lambda: [jax.device_put(v) for v in views])
    del views, src, dst
    store = timed("from_edges.total", lambda: harness.build_store(
        graph, config, traffic, *edges))
    for name in dict.fromkeys(s["name"] for s in traffic["round"]
                              if s["kind"] == "property"):
        one = dict(traffic, round=[
            s for s in traffic["round"]
            if s["kind"] != "property" or s["name"] == name])
        timed(f"init.{name}", lambda: harness.build_service(
            store, graph, config, one)[0].states())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

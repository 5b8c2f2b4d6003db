"""The controls of the check: answers that break a guarantee the
configurations state, put in the place of the program's, and judged by the
harness's own ``check`` with the same limits.  A sound check says
``correct: false`` for every control.

* a stale read, for the guarantee that an acknowledged update is visible to
  the next read: each membership read answered as of the epoch before the
  last update (``member_mismatch``);
* PageRank computed by the reference in bfloat16, the precision below the
  float32 the configurations state, over the same live edges as the
  program's last read (``pagerank_l1``).

    python3 -m bench.control --workload urand-s18.fresh --seeds 1,2,3 \\
        --seconds 10 --trace 0

For each seed, in one process, it runs the cell as a benchmark run does
(set-up, a window of ``--seconds``, traced with ``--trace 1``, the check)
and prints one JSON line: the run's result line with what the check says
of the control's answers added (``control``).  It needs the chips the cell
asks for.
"""
from __future__ import annotations

import time
import types

import numpy as np

from . import reference

# the PageRank property's stopping rule: an L1 step of at most 1e-5, at
# most 100 steps
PAGERANK_MARGIN = 1e-5
PAGERANK_MAX_ITER = 100


def pagerank_bf16(n: int, src, dst, *, damping: float) -> np.ndarray:
    """``reference.ref_pagerank``'s power iteration with every array and
    every sum in bfloat16, stopped by the PageRank property's rule."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16

    @jax.jit
    def solve(s, d):
        out = jnp.zeros(n, bf).at[s].add(jnp.ones(s.shape, bf))
        sink = out == 0
        inv = jnp.where(sink, bf(0), bf(1) / jnp.maximum(out, bf(1)))
        base = bf((1.0 - damping) / n)

        def step(carry):
            pr, _, i = carry
            sums = jnp.zeros(n, bf).at[d].add((pr * inv)[s])
            dangling = jnp.sum(jnp.where(sink, pr, bf(0)), dtype=bf)
            new = base + bf(damping) * (sums + dangling / bf(n))
            return new, jnp.sum(jnp.abs(new - pr), dtype=bf), i + 1

        def more(carry):
            _, delta, i = carry
            return (delta > PAGERANK_MARGIN) & (i < PAGERANK_MAX_ITER)

        pr0 = jnp.full(n, 1.0 / n, bf)
        return jax.lax.while_loop(more, step, (pr0, bf(jnp.inf), 0))[0]

    pr = solve(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
    return np.asarray(pr, np.float64)


def answers(*, config, rounds, served, n_vertices, live) -> list:
    """``served`` with the control's answers in the program's place: every
    membership read one epoch stale, and the last PageRank read in
    bfloat16 over the ``live`` arc keys (``harness.run_cell``'s hook)."""
    pagerank_at = None
    out = []
    for rnd, rec in zip(rounds.rounds, served):
        requests = []
        for item, q in zip(rnd["requests"], rec["requests"]):
            if item["kind"] == "member":
                q = dict(q, response=types.SimpleNamespace(
                    kind=q["response"].kind, payload={"found": item["stale"]}))
            elif item.get("name") == "pagerank":
                pagerank_at = (len(out), len(requests))
            requests.append(q)
        out.append(dict(rec, requests=requests))
    if pagerank_at is not None:
        r, i = pagerank_at
        src, dst = reference.split_keys(live)
        pr = pagerank_bf16(n_vertices, src, dst,
                           damping=config["analytics"]["pagerank"]["damping"])
        q = out[r]["requests"][i]
        out[r]["requests"][i] = dict(q, response=types.SimpleNamespace(
            kind=q["response"].kind, payload={"value": pr}))
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, run one after another")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[control] {msg}", file=sys.stderr, flush=True)

    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(run.ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    bench = harness.Bench(run.ROOT)
    cell = bench.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s)")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(
            bench, args.workload, seed, args.seconds, bool(args.trace),
            devices=devices[:cell["chips"]], t_start=time.perf_counter(),
            log=log, control=answers)
        print(json.dumps(dict(result, workload=args.workload, seed=seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

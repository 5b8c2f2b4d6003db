"""Sharded single-program plane A/B — BENCH_sharded.json.

Comparisons on an 8-virtual-device host mesh (the same
``--xla_force_host_platform_device_count=8`` rig as the multidevice test):

* ``mixed_stream`` — the legacy sharded update path (owner routing +
  per-op ``vmap(B.insert_edges)`` / ``vmap(B.delete_edges)``, functional
  pool copies, two dispatches per round) vs the engine-backed path
  (``apply_update_sharded``: one fused, donated dispatch per round).
  Final pools are asserted leaf-for-leaf identical; the engine must not
  lose.
* ``store_apply_8shard_vs_1shard`` — the acceptance row:
  ``ShardedGraphStore.apply`` under shard_map dispatch (8 shards, one
  single-program epoch: on-device all-to-all routing + every view's
  delete/insert + epoch close) vs the 1-shard ``GraphStore.apply`` on the
  same sliding-window mixed stream (each round inserts a uniform batch and
  deletes the batch inserted two rounds earlier — the classic windowed
  dynamic-graph workload; deletes are balanced across owners).  Must reach
  speedup >= 1.0; the shard_map and vmap-fallback final pools are asserted
  leaf-for-leaf identical.
* ``store_apply_..._hubdel`` — transparency row, NOT gated: the same
  stream but with deletes sampled uniformly from the rmat edge list.
  Power-law hubs concentrate deletes onto single owners, so the per-owner
  bucket-max width (the SPMD batch width every shard pays) inflates ~3-4x
  over the mean — the adversarial regime for vertex partitioning.  The row
  documents it instead of hiding it.
* ``store_scaling_S{n}`` — the acceptance stream at S in {1, 2, 4, 8}
  shard_map shards vs the same 1-shard baseline.
* ``phase_*`` — per-epoch phase breakdown of the single program at S=8,
  via standalone probe programs: collective exchange alone, routing
  (sort + exchange + compaction), engine dispatch (full program minus
  routing), and host overhead (wall clock minus device program).
* ``sweep_*`` — distributed analytics super-step throughput under
  shard_map dispatch vs the single-graph engines on the unsharded union.
  Must reach speedup >= 1.0; WCC labels are asserted bit-identical across
  1-shard/vmap/shard_map, PageRank bit-identical between dispatch modes
  (vs 1-shard: allclose — the per-shard sweep regroups the f32 sums).

XLA locks the device count at first init, so on a CPU-pinned host
(``JAX_PLATFORMS=cpu``) ``run()`` re-execs this module in a subprocess with
the forced-device env (benchmarks.run stays usable in-process); on an
accelerator host it runs in-process on the real devices.  Absolute times
on a host-platform mesh are NOT a model of TPU all-to-all cost — the 8
virtual devices serialize on the host cores, so every ratio here is a
lower bound on real-mesh scaling: the ratios track engine-vs-legacy work,
not the wire.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

_OUT = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sharded.json"


def run(scale: str = "quick"):
    """benchmarks.run entry point.  Where the environment pins JAX to the
    CPU (``JAX_PLATFORMS=cpu``, decided before JAX is touched) re-exec with
    8 forced host devices, since XLA fixes the device count at first use;
    on an accelerator host run in-process on the real devices: a child
    process could not reach a chip this process holds."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        _main(scale)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_bench", "--scale", scale],
        env=env, cwd=pathlib.Path(__file__).resolve().parent.parent)
    if out.returncode != 0:
        raise RuntimeError(f"sharded_bench subprocess failed "
                           f"(rc={out.returncode})")


def _main(scale: str):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    import dataclasses

    from repro.algorithms import pagerank, wcc_labelprop_sweep
    from repro.core import batch as B
    from repro.core import from_edges_host
    from repro.data.synth import rmat_edges
    from repro.distributed.collectives import exchange_buckets
    from repro.distributed.sharded_graph import (SHARD_AXIS,
                                                 apply_update_sharded,
                                                 ensure_capacity_sharded,
                                                 max_owner_count,
                                                 pagerank_sharded,
                                                 place_on_mesh,
                                                 route_edges, route_exchange,
                                                 routing_cap_blocks,
                                                 shard_from_edges_host,
                                                 shard_mesh,
                                                 wcc_sharded)
    from repro.stream import GraphStore, ShardedGraphStore
    from repro.stream.sharded_store import _cap_rung

    from .timing import row

    S = min(8, len(jax.devices()))
    if S < 2:
        raise RuntimeError(f"the sharded bench needs at least 2 devices; "
                           f"{jax.default_backend()} has {S}")
    # streams run at a bulk-update scale (the regime the single-program
    # plane is for); "full" additionally grows the graph
    V, E, bs, rounds = ((1 << 15, 240000, 8192, 3) if scale == "quick"
                        else (1 << 17, 1000000, 8192, 4))
    lag = 2          # sliding window: round t deletes the round t-lag batch
    rng = np.random.default_rng(33)
    src, dst = rmat_edges(V, E, seed=33)
    E = len(src)

    mesh = shard_mesh(S)

    def copy_sg(sg):
        return dataclasses.replace(
            sg, graphs=jax.tree.map(jnp.array, sg.graphs))

    def tree_equal(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    def median(ts):
        ts = sorted(ts)
        return ts[len(ts) // 2]

    results = []

    def record(name, old_us, new_us, extra=""):
        results.append({"name": name, "old_us": round(old_us, 1),
                        "new_us": round(new_us, 1),
                        "speedup": round(old_us / new_us, 3)})
        row(f"sharded_{name}_old", old_us)
        row(f"sharded_{name}_new", new_us,
            f"speedup={old_us / new_us:.2f}x" + (f";{extra}" if extra else ""))

    # -- workloads ----------------------------------------------------------
    # sliding-window stream: uniform inserts, deletes = the batch inserted
    # `lag` rounds earlier (balanced per-owner delete counts)
    uni = [(rng.integers(0, V, bs).astype(np.uint32),
            rng.integers(0, V, bs).astype(np.uint32))
           for _ in range(rounds + lag)]
    window_warm = [dict(ins_src=u[0], ins_dst=u[1]) for u in uni[:lag]]
    window_batches = [dict(ins_src=uni[t + lag][0], ins_dst=uni[t + lag][1],
                           del_src=uni[t][0], del_dst=uni[t][1])
                      for t in range(rounds)]
    # hub-skewed stream: deletes sampled from the rmat edge list
    del_idx = [rng.choice(E, bs, replace=False) for _ in range(rounds)]
    hub_batches = [dict(ins_src=uni[t + lag][0], ins_dst=uni[t + lag][1],
                        del_src=src[del_idx[t]], del_dst=dst[del_idx[t]])
                   for t in range(rounds)]

    # -- mixed update stream: legacy vmap-per-op vs fused donated engine ----
    sg0 = ensure_capacity_sharded(shard_from_edges_host(V, S, src, dst),
                                  (rounds + 1) * bs + 64)
    stream_pairs = [((jnp.asarray(b["del_src"]), jnp.asarray(b["del_dst"])),
                     (jnp.asarray(b["ins_src"]), jnp.asarray(b["ins_dst"])))
                    for b in hub_batches]

    def legacy_step(sg, dels, ins):
        # the pre-engine path: route + one vmapped engine entry per op,
        # no donation (a functional copy of every shard pool per op)
        ds, dd, _, _, _ = route_edges(dels[0], dels[1], n_shards=S, cap=bs)
        graphs, _ = jax.vmap(B.delete_edges)(sg.graphs, ds, dd)
        sg = dataclasses.replace(sg, graphs=graphs)
        bsrc, bdst, _, _, _ = route_edges(ins[0], ins[1], n_shards=S, cap=bs)
        graphs, _ = jax.vmap(B.insert_edges)(sg.graphs, bsrc, bdst)
        return dataclasses.replace(sg, graphs=graphs)

    def engine_step(sg, dels, ins):
        sg, _, _ = apply_update_sharded(sg, ins[0], ins[1], None,
                                        dels[0], dels[1], cap=bs,
                                        donate=True)
        return sg

    def stream(step, iters=3):
        ts, out = [], None
        for _ in range(iters):
            sg = copy_sg(sg0)
            jax.block_until_ready(sg.graphs.keys)
            t0 = time.perf_counter()
            for dels, ins in stream_pairs:
                sg = step(sg, dels, ins)
            jax.block_until_ready(sg.graphs.keys)
            ts.append(time.perf_counter() - t0)
            out = sg
        return median(ts) * 1e6, out

    old_us, g_old = stream(legacy_step)
    new_us, g_new = stream(engine_step)
    assert tree_equal(g_old.graphs, g_new.graphs), \
        "sharded engine/legacy pool disagreement"
    record(f"mixed_stream_b{bs}", old_us / rounds, new_us / rounds,
           f"Meps={2 * bs / (new_us / rounds):.2f}")
    assert new_us <= old_us, \
        f"engine-backed sharded apply lost to legacy: {new_us} vs {old_us}"

    # -- store apply: shard_map single-program epochs vs 1-shard store ------
    def store_stream(make, batches, iters=3):
        st = make()          # compile pass on throwaway state
        for b in window_warm + batches:
            st.apply(**b)
        ts = []
        for _ in range(iters):
            st = make()
            for b in window_warm:
                st.apply(**b)
            jax.block_until_ready(jax.tree.leaves(st.forward)[0])
            t0 = time.perf_counter()
            for b in batches:
                st.apply(**b)
            jax.block_until_ready(jax.tree.leaves(st.forward)[0])
            ts.append(time.perf_counter() - t0)
        return median(ts) * 1e6, st

    def make_one():
        return GraphStore.from_edges(
            V, src, dst, hashing=False,
            slack_slabs=(rounds + lag + 1) * bs // 16)

    def make_sharded(n_shards=S, dispatch="auto"):
        def make():
            st = ShardedGraphStore.from_edges(V, n_shards, src, dst,
                                              dispatch=dispatch)
            if dispatch != "vmap":
                st.place_on_mesh(shard_mesh(n_shards))
            return st
        return make

    one_us, _ = store_stream(make_one, window_batches)
    sm_us, st_sm = store_stream(make_sharded(), window_batches)
    sv_us, st_sv = store_stream(make_sharded(dispatch="vmap"),
                                window_batches)
    assert tree_equal(tuple(st_sm.views[r].graphs for r in st_sm.views),
                      tuple(st_sv.views[r].graphs for r in st_sv.views)), \
        "shard_map/vmap final pools disagree"
    record("store_apply_8shard_vs_1shard", one_us / rounds, sm_us / rounds,
           f"batch={bs}ins+{bs}del;window;recompiles={st_sm.recompile_count}")
    record("store_apply_8shard_vs_1shard_vmap_fallback",
           one_us / rounds, sv_us / rounds,
           f"window;recompiles={st_sv.recompile_count}")

    one_hub_us, _ = store_stream(make_one, hub_batches)
    hub_us, st_hub = store_stream(make_sharded(), hub_batches)
    record("store_apply_8shard_vs_1shard_hubdel",
           one_hub_us / rounds, hub_us / rounds,
           "rmat-sampled deletes: per-owner bucket-max width inflates "
           "~3-4x under hub skew")

    # -- shard scaling on the acceptance stream -----------------------------
    for n_shards in (1, 2, 4, 8):
        if n_shards > S:
            continue
        if n_shards == S:
            s_us = sm_us     # same config as the acceptance row — reuse
        else:
            s_us, _ = store_stream(make_sharded(n_shards), window_batches)
        record(f"store_scaling_S{n_shards}", one_us / rounds, s_us / rounds,
               "window;single-program shard_map")

    # -- phase breakdown of the single-program epoch at S=8 -----------------
    # standalone probes at the acceptance-stream caps; engine time is the
    # full-program residual over routing, host overhead the wall-clock
    # residual over the device program
    d_s, d_d = window_batches[0]["del_src"], window_batches[0]["del_dst"]
    i_s, i_d = window_batches[0]["ins_src"], window_batches[0]["ins_dst"]
    caps = {}
    for slot, arr in (("del_s", d_s), ("del_d", d_d),
                      ("ins_s", i_s), ("ins_d", i_d)):
        caps[slot] = (routing_cap_blocks(arr, S, bs // S),
                      _cap_rung(max_owner_count(arr, S)))
    probe_args = tuple(jnp.asarray(a) for a in (d_s, d_d, i_s, i_d))
    vec = P(SHARD_AXIS)

    def route_probe(ds_l, dd_l, is_l, id_l):
        outs = []
        for s, d, cap in ((ds_l, dd_l, caps["del_s"]),
                          (dd_l, ds_l, caps["del_d"]),
                          (is_l, id_l, caps["ins_s"]),
                          (id_l, is_l, caps["ins_d"])):
            bs_, bd_, _, orig, _ = route_exchange(s, d, None, n_shards=S,
                                                  cap=cap[0])
            perm = jnp.argsort(orig < 0, stable=True)[:cap[1]]
            outs.append(bs_[perm] ^ bd_[perm])
        return jnp.concatenate(outs)[None]

    def exchange_probe(ds_l, dd_l, is_l, id_l):
        outs = []
        for s, cap in ((ds_l, caps["del_s"]), (dd_l, caps["del_d"]),
                       (is_l, caps["ins_s"]), (id_l, caps["ins_d"])):
            blk = jnp.resize(s, (S, cap[0]))
            outs.append(exchange_buckets(blk, SHARD_AXIS).reshape(-1))
        return jnp.concatenate(outs)[None]

    def probe_time(fn, n=10):
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(vec,) * 4,
                              out_specs=P(SHARD_AXIS, None),
                              check_vma=False))
        jax.block_until_ready(f(*probe_args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(*probe_args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e6

    t_exchange = probe_time(exchange_probe)
    t_route = probe_time(route_probe)
    epoch_us = sm_us / rounds
    # device program time: one donated single-program epoch re-dispatched
    # on the final store state (compiled path, median of repeats)
    st_p = make_sharded()()
    for b in window_warm + window_batches:
        st_p.apply(**b)
    ts = []
    for t in range(5):
        b = window_batches[t % rounds]
        jax.block_until_ready(jax.tree.leaves(st_p.forward)[0])
        t0 = time.perf_counter()
        st_p.apply(**b)
        jax.block_until_ready(jax.tree.leaves(st_p.forward)[0])
        ts.append(time.perf_counter() - t0)
    t_program = median(ts) * 1e6
    phases = {
        "exchange_us": round(t_exchange, 1),
        "route_us": round(max(t_route - t_exchange, 0.0), 1),
        "engine_dispatch_us": round(max(t_program - t_route, 0.0), 1),
        "host_overhead_us": round(max(epoch_us - t_program, 0.0), 1),
    }
    for k, v in phases.items():
        row(f"sharded_phase_{k}", v)

    # -- sweep throughput: distributed analytics vs unsharded union ---------
    def sweep_time(fn, iters, n=3):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return median(ts) * 1e6 / iters

    g_in = from_edges_host(V, dst, src, hashing=False)
    sg_in_v = shard_from_edges_host(V, S, dst, src)
    sg_in_m = place_on_mesh(copy_sg(sg_in_v), mesh)
    out_deg = jnp.asarray(from_edges_host(V, src, dst,
                                          hashing=False).degree)

    iters = 20
    pr_one = pagerank(g_in, out_deg, max_iter=iters, error_margin=0.0)[0]
    pr_v = pagerank_sharded(sg_in_v, out_deg, max_iter=iters,
                            error_margin=0.0)[0]
    pr_m = pagerank_sharded(sg_in_m, out_deg, max_iter=iters,
                            error_margin=0.0)[0]
    assert np.array_equal(np.asarray(pr_v), np.asarray(pr_m)), \
        "pagerank dispatch modes disagree bitwise"
    np.testing.assert_allclose(np.asarray(pr_m), np.asarray(pr_one),
                               atol=1e-5)
    t_old = sweep_time(lambda: pagerank(g_in, out_deg, max_iter=iters,
                                        error_margin=0.0)[0], iters)
    t_new = sweep_time(lambda: pagerank_sharded(sg_in_m, out_deg,
                                                max_iter=iters,
                                                error_margin=0.0)[0], iters)
    record("sweep_pagerank", t_old, t_new, f"us_per_superstep;S={S}")

    # wcc sweeps over the symmetric union (labels bit-identical — asserted)
    s2 = np.concatenate([src, dst])
    d2 = np.concatenate([dst, src])
    g_sym = from_edges_host(V, s2, d2, hashing=False)
    sg_sym_v = shard_from_edges_host(V, S, s2, d2)
    sg_sym_m = place_on_mesh(copy_sg(sg_sym_v), mesh)
    lab_old, it_old = wcc_labelprop_sweep(g_sym)
    lab_v, _ = wcc_sharded(sg_sym_v)
    lab_m, it_new = wcc_sharded(sg_sym_m)
    assert np.array_equal(np.asarray(lab_old), np.asarray(lab_m))
    assert np.array_equal(np.asarray(lab_v), np.asarray(lab_m))
    t_old = sweep_time(lambda: wcc_labelprop_sweep(g_sym)[0], int(it_old))
    t_new = sweep_time(lambda: wcc_sharded(sg_sym_m)[0], int(it_new))
    record("sweep_wcc", t_old, t_new, f"us_per_superstep;S={S}")

    # -- acceptance gates ---------------------------------------------------
    gated = {"store_apply_8shard_vs_1shard", "sweep_pagerank", "sweep_wcc"}
    for r in results:
        if r["name"] in gated:
            assert r["speedup"] >= 1.0, \
                f"{r['name']} below parity: {r['speedup']}x"

    payload = {
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "scale": scale,
        "graph": {"V": V, "E": int(E), "shards": S,
                  "batch": bs, "rounds": rounds},
        "phases": phases,
        "note": ("host-platform 8-device mesh (devices serialize on the "
                 "host cores — ratios are a lower bound on real-mesh "
                 "scaling); old = legacy sharded path / 1-shard store / "
                 "unsharded analytics; new = single-program shard_map "
                 "plane (one donated epoch program: all-to-all routing + "
                 "every view's delete/insert + epoch close; collective "
                 "exchange sweeps).  store_apply rows use the "
                 "sliding-window stream; the _hubdel row keeps the "
                 "skew-adversarial rmat-delete workload visible."),
        "results": results,
    }
    _OUT.write_text(json.dumps(payload, indent=2) + "\n")
    row("sharded_bench_json", 0.0, str(_OUT.name))


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick")
    _main(ap.parse_args().scale)

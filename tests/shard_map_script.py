"""Executed by test_shard_map.py in a subprocess with 8 forced host devices
(XLA locks the device count at first init, so this cannot run inside the
main pytest process).

Proves the single-program sharded plane (DESIGN.md §9): epochs dispatched
as ONE shard_map program over the ("shard",) mesh — on-device all-to-all
routing, collective exchanges, donated pools — produce pools LEAF-FOR-LEAF
identical to the stacked-vmap fallback, across mixed / skewed /
delete-only / insert-only / weighted epochs with V % S != 0, and analytics
bit-identical between dispatch modes.

With SHARD_MAP_PERF=1 (the CI smoke step) it additionally asserts the
sharded shard_map sweep does not lose to the 1-shard sweep.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.distributed.sharded_graph import shard_mesh
from repro.stream import GraphStore, ShardedGraphStore

assert len(jax.devices()) == 8, jax.devices()

S, V = 8, 53             # V % S != 0: tail-clamped local id spaces
rng = np.random.default_rng(0)
src = rng.integers(0, V, 400).astype(np.uint32)
dst = rng.integers(0, V, 400).astype(np.uint32)
keep = src != dst
src, dst = src[keep], dst[keep]
mesh = shard_mesh(S)

sv = ShardedGraphStore.from_edges(V, S, src, dst, dispatch="vmap")
sm = ShardedGraphStore.from_edges(V, S, src, dst).place_on_mesh(mesh)
us = GraphStore.from_edges(V, src, dst)
assert sm._mode() == "shard_map" and sv._mode() == "vmap"


def leaves_equal(a, b):
    la, lb = jax.tree.leaves(a.graphs), jax.tree.leaves(b.graphs)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


uniq = set(zip(src.tolist(), dst.tolist()))
for ep in range(4):
    if ep == 2:
        # skewed: every insert owned by shard 3 (one all-to-all bucket row
        # carries the whole batch)
        ins = np.stack([(rng.integers(0, V // S, 80) * S + 3) % V,
                        rng.integers(0, V, 80)], 1).astype(np.uint32)
    else:
        ins = rng.integers(0, V, (120, 2)).astype(np.uint32)
    ins = ins[ins[:, 0] != ins[:, 1]]
    cur = (np.array(sorted(uniq), np.uint32) if uniq
           else np.zeros((0, 2), np.uint32))
    dels = (cur[rng.choice(len(cur), min(30, len(cur)), replace=False)]
            if len(cur) else np.zeros((0, 2), np.uint32))
    if ep == 3:
        obs.trace.enable()               # the last epoch counts its probes
    bv = sv.apply(ins[:, 0], ins[:, 1], None, dels[:, 0], dels[:, 1])
    bm = sm.apply(ins[:, 0], ins[:, 1], None, dels[:, 0], dels[:, 1])
    bu = us.apply(ins[:, 0], ins[:, 1], None, dels[:, 0], dels[:, 1])
    obs.trace.disable()
    assert bv.n_inserted == bm.n_inserted == bu.n_inserted, \
        (ep, bv.n_inserted, bm.n_inserted, bu.n_inserted)
    assert bv.n_deleted == bm.n_deleted == bu.n_deleted
    for name in sv.views:
        assert leaves_equal(sv.views[name], sm.views[name]), (ep, name)
    uniq -= {(int(a), int(b)) for a, b in dels}
    uniq |= {(int(a), int(b)) for a, b in ins}
    q = rng.integers(0, V, (200, 2)).astype(np.uint32)
    assert np.array_equal(sm.query(q[:, 0], q[:, 1]),
                          us.query(q[:, 0], q[:, 1])), ep
print("OK mixed epochs: shard_map pools leaf-for-leaf == vmap pools; "
      "queries track unsharded store")


def probe_loops(c):
    """loop -> its counters, from one span's ``probe.<loop>.<k>``."""
    loops = {}
    for k, v in c.items():
        if k.startswith("probe."):
            loop, field = k.rsplit(".", 1)
            loops.setdefault(loop, {})[field] = v
    return loops


def check_counts(cv, cm):
    """Both renderings walk the same chains: the same hops and visits per
    probe loop.  Widths, and so the rows gathered and the packs, differ
    only where the shard_map plane compacts a bucket."""
    assert set(cv) == set(cm), (sorted(cv), sorted(cm))
    for loop in cv:
        v, m = cv[loop], cm[loop]
        assert set(v) == set(m) == {"hops", "visits", "width", "rows",
                                    "packs"}, sorted(v)
        assert (v["hops"], v["visits"]) == (m["hops"], m["visits"]), loop
        if v["width"] == m["width"]:
            assert (v["rows"], v["packs"]) == (m["rows"], m["packs"]), loop
        for c in (v, m):
            assert c["visits"] <= c["rows"] <= c["hops"] * c["width"], loop


cv, cm, cu = (probe_loops(c) for c in
              obs.trace.span_counters()["store.apply.dispatch"])
assert set(cv) == set(cu) and len(cv) == 7, sorted(cv)
check_counts(cv, cm)
print("OK probe counters: shard_map hops/visits == vmap hops/visits")

# a hub whose chain outlasts the rest of a wide batch: the probe loops pack
# their live lanes on every shard that holds it, inside shard_map as under
# vmap, and the pools stay leaf-for-leaf identical
VH, HUB = 4096, 5
hsrc = np.concatenate([np.full(1500, HUB), rng.integers(0, VH, 3000)])
hdst = np.concatenate([rng.choice(np.arange(HUB + 1, VH), 1500,
                                  replace=False), rng.integers(0, VH, 3000)])
keep = hsrc != hdst
hsrc, hdst = hsrc[keep].astype(np.uint32), hdst[keep].astype(np.uint32)
hv = ShardedGraphStore.from_edges(VH, S, hsrc, hdst, dispatch="vmap")
hm = ShardedGraphStore.from_edges(VH, S, hsrc, hdst).place_on_mesh(mesh)
hu = GraphStore.from_edges(VH, hsrc, hdst)
ins = np.stack([np.where(np.arange(2048) % 4 == 0, HUB,
                         rng.integers(0, VH, 2048)),
                rng.integers(0, VH, 2048)], 1).astype(np.uint32)
ins = ins[ins[:, 0] != ins[:, 1]]
q = np.concatenate([ins[:1024], np.stack([hsrc[:1024], hdst[:1024]], 1)])
obs.trace.enable()
hv.apply(ins[:, 0], ins[:, 1])
hm.apply(ins[:, 0], ins[:, 1])
obs.trace.disable()
for name in hv.views:
    assert leaves_equal(hv.views[name], hm.views[name]), name
assert np.array_equal(hm.query(q[:, 0], q[:, 1]), hv.query(q[:, 0], q[:, 1]))
hu.apply(ins[:, 0], ins[:, 1])
assert np.array_equal(hm.query(q[:, 0], q[:, 1]), hu.query(q[:, 0], q[:, 1]))
cv, cm = (probe_loops(c) for c in
          obs.trace.span_counters()["store.apply.dispatch"][-2:])
check_counts(cv, cm)
for c in (cv, cm):
    ins_loop = c["probe.forward.insert"]
    assert ins_loop["packs"] >= 1, ins_loop
    assert ins_loop["rows"] < ins_loop["hops"] * ins_loop["width"], ins_loop
print("OK hub epoch: packed probe loops, shard_map pools == vmap pools")
print("recompiles: vmap", sv.recompile_count,
      "shard_map", sm.recompile_count)

# delete-only epoch and insert-only epoch
cur = np.array(sorted(uniq), np.uint32)
dels = cur[:16]
sv.apply(None, None, None, dels[:, 0], dels[:, 1])
sm.apply(None, None, None, dels[:, 0], dels[:, 1])
ins = rng.integers(0, V, (40, 2)).astype(np.uint32)
ins = ins[ins[:, 0] != ins[:, 1]]
sv.apply(ins[:, 0], ins[:, 1])
sm.apply(ins[:, 0], ins[:, 1])
for name in sv.views:
    assert leaves_equal(sv.views[name], sm.views[name]), name
print("OK delete-only / insert-only epochs identical")

# weighted store
wsrc = rng.integers(0, V, 100).astype(np.uint32)
wdst = rng.integers(0, V, 100).astype(np.uint32)
k = wsrc != wdst
wsrc, wdst = wsrc[k], wdst[k]
w = rng.random(len(wsrc)).astype(np.float32)
wv = ShardedGraphStore.from_edges(V, S, wsrc, wdst, w, dispatch="vmap")
wm = ShardedGraphStore.from_edges(V, S, wsrc, wdst, w).place_on_mesh(mesh)
ins = rng.integers(0, V, (50, 2)).astype(np.uint32)
ins = ins[ins[:, 0] != ins[:, 1]]
iw = rng.random(len(ins)).astype(np.float32)
wv.apply(ins[:, 0], ins[:, 1], iw, wsrc[:10], wdst[:10])
wm.apply(ins[:, 0], ins[:, 1], iw, wsrc[:10], wdst[:10])
for name in wv.views:
    assert leaves_equal(wv.views[name], wm.views[name]), name
print("OK weighted epochs identical")

# properties on the mesh-placed store are bitwise identical across modes
from repro.stream.sharded_store import (sharded_pagerank_property,
                                        sharded_wcc_property)
from repro.stream.properties import PropertyRegistry

reg = PropertyRegistry(sm)
reg.register(sharded_pagerank_property(max_iter=40))
reg.register(sharded_wcc_property())
reg2 = PropertyRegistry(sv)
reg2.register(sharded_pagerank_property(max_iter=40))
reg2.register(sharded_wcc_property())
assert np.array_equal(np.asarray(reg.read("pagerank")),
                      np.asarray(reg2.read("pagerank")))
assert np.array_equal(np.asarray(reg.read("wcc")),
                      np.asarray(reg2.read("wcc")))
print("OK properties bitwise identical across dispatch modes")

# analytics dispatch identity on a larger rmat graph
from repro.algorithms import bfs_vanilla, pagerank, wcc_labelprop_sweep
from repro.core import from_edges_host
from repro.data.synth import rmat_edges
from repro.distributed.sharded_graph import (bfs_sharded, pagerank_sharded,
                                             place_on_mesh,
                                             shard_from_edges_host,
                                             wcc_sharded)

Vg, Eg = 1 << 13, 60000
gsrc, gdst = rmat_edges(Vg, Eg, seed=33)
g_in = from_edges_host(Vg, gdst, gsrc, hashing=False)
out_deg = jnp.asarray(from_edges_host(Vg, gsrc, gdst,
                                      hashing=False).degree)
sg_v = shard_from_edges_host(Vg, S, gdst, gsrc)
sg_m = place_on_mesh(shard_from_edges_host(Vg, S, gdst, gsrc), mesh)

pr_v, _ = pagerank_sharded(sg_v, out_deg, max_iter=30, error_margin=0.0)
pr_m, _ = pagerank_sharded(sg_m, out_deg, max_iter=30, error_margin=0.0)
assert np.array_equal(np.asarray(pr_v), np.asarray(pr_m))
pr_1, _ = pagerank(g_in, out_deg, max_iter=30, error_margin=0.0)
np.testing.assert_allclose(np.asarray(pr_m), np.asarray(pr_1), atol=1e-5)

d_v, _ = bfs_sharded(sg_v, src=0)
d_m, _ = bfs_sharded(sg_m, src=0)
assert np.array_equal(np.asarray(d_v), np.asarray(d_m))

s2 = np.concatenate([gsrc, gdst])
d2 = np.concatenate([gdst, gsrc])
sgs_v = shard_from_edges_host(Vg, S, s2, d2)
sgs_m = place_on_mesh(shard_from_edges_host(Vg, S, s2, d2), mesh)
lab_v, _ = wcc_sharded(sgs_v)
lab_m, _ = wcc_sharded(sgs_m)
lab_1, _ = wcc_labelprop_sweep(from_edges_host(Vg, s2, d2, hashing=False))
assert np.array_equal(np.asarray(lab_v), np.asarray(lab_m))
assert np.array_equal(np.asarray(lab_m), np.asarray(lab_1))
print("OK analytics bit-identical between dispatch modes "
      "(pagerank also vs 1-shard at 1e-5)")

# the served path as ``serve --shards 4`` builds it: a 4-shard store placed
# on a 4-device mesh behind the request pipeline, every read checked against
# the numpy reference over the live edge set (chip_smoke.py's CPU rehearsal)
import contextlib
import importlib.util
import io

spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(os.path.dirname(__file__), "..",
                               "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(chip_smoke)
log = io.StringIO()
with contextlib.redirect_stdout(log):
    rc = chip_smoke.main(["--allow-cpu", "--chips", "4", "--scale", "11",
                          "--batch", "1024"])
lines = log.getvalue().splitlines()
assert rc == 0, log.getvalue()
for what in ("membership 5", "bfs levels", "wcc partition",
             "forward placement", "symmetric placement"):
    assert any(what in line and " ok " in line for line in lines), what
print("OK served path: 4-shard store on a 4-device mesh answers membership, "
      "BFS and WCC reads equal to the numpy reference")

if os.environ.get("SHARD_MAP_PERF") == "1":
    # CI smoke gate: the sharded shard_map sweep must not lose to the
    # 1-shard sweep (headroom is ~2x on this workload — see
    # BENCH_sharded.json — so the gate is robust to runner noise)
    import time

    def med_time(fn, n=5):
        jax.block_until_ready(fn())
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[n // 2]

    t_one = med_time(lambda: pagerank(g_in, out_deg, max_iter=30,
                                      error_margin=0.0)[0])
    t_sm = med_time(lambda: pagerank_sharded(sg_m, out_deg, max_iter=30,
                                             error_margin=0.0)[0])
    print(f"sweep perf: 1-shard {t_one * 1e3:.1f} ms, "
          f"shard_map {t_sm * 1e3:.1f} ms ({t_one / t_sm:.2f}x)")
    assert t_sm <= t_one, \
        f"sharded sweep lost to 1-shard sweep: {t_sm:.4f}s vs {t_one:.4f}s"
    print("OK sharded sweep >= 1-shard sweep")

print("ALL SHARD_MAP CHECKS PASSED")

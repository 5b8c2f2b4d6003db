"""Executed by test_multidevice.py in a subprocess with 8 host devices.

Proves the distribution layer RUNS (not just compiles): sharded LM train
step, vertex-sharded dynamic graph, elastic checkpoint restore across mesh
shapes.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

assert len(jax.devices()) == 8, jax.devices()

# ---------------------------------------------------------------------------
# 1. sharded LM train step actually runs
# ---------------------------------------------------------------------------
from repro.configs import get_arch
from repro.distributed.sharding import sharding_rules
from repro.launch.steps import build_lm_train_step, lm_param_specs, lm_opt_specs
from repro.models import transformer as tfm
from repro.train import optimizer as opt

mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
cfg = get_arch("gemma2-9b").smoke_config()
key = jax.random.PRNGKey(0)
params = tfm.init_params(cfg, key)
ostate = opt.init(params)
toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                            cfg.vocab_size)

# smoke dims aren't 16-divisible → replicate params, shard batch only
pspec = jax.tree.map(lambda _: P(), params)
ospec = jax.tree.map(lambda _: P(), ostate)
with sharding_rules(mesh, {"act_btd": P("data", None, None),
                           "logits": P("data", None, None),
                           "moe_ecd": None}):
    step = jax.jit(build_lm_train_step(cfg),
                   in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s), pspec),
                                 jax.tree.map(lambda s: NamedSharding(mesh, s), ospec),
                                 NamedSharding(mesh, P("data", None)),
                                 NamedSharding(mesh, P("data", None))))
    with mesh:
        p2, o2, loss = step(params, ostate, toks, labels)
loss_sharded = float(loss)
p2u, o2u, loss_unsharded = jax.jit(build_lm_train_step(cfg))(
    params, ostate, toks, labels)
assert np.isfinite(loss_sharded)
assert abs(loss_sharded - float(loss_unsharded)) < 1e-3, \
    (loss_sharded, float(loss_unsharded))
print("OK sharded LM train step: loss", loss_sharded)

# ---------------------------------------------------------------------------
# 2. vertex-sharded dynamic graph on the device grid
# ---------------------------------------------------------------------------
from repro.core import from_edges_host, query_edges
from repro.distributed.sharded_graph import (bfs_sharded,
                                             insert_edges_sharded,
                                             pagerank_sharded,
                                             query_edges_sharded, shard_empty,
                                             wcc_sharded)
import dataclasses

rng = np.random.default_rng(0)
V, S = 251, 8            # V % S != 0: tail-padded local id spaces
src = rng.integers(0, V, 2000).astype(np.uint32)
dst = rng.integers(0, V, 2000).astype(np.uint32)
keep = src != dst
src, dst = src[keep], dst[keep]

sg = shard_empty(V, S, capacity_slabs_per_shard=256)
# place every shard's arrays across the 8 devices (leading dim = shard)
flat_mesh = jax.make_mesh((8,), ("shard",), axis_types=(AxisType.Auto,))
def place(x):
    if x.ndim == 0:
        return x
    return jax.device_put(x, NamedSharding(flat_mesh, P(*(("shard",) + (None,) * (x.ndim - 1)))))
def place_sg(sg):
    return dataclasses.replace(sg, graphs=jax.tree.map(place, sg.graphs))
sg = place_sg(sg)

sg, ins = insert_edges_sharded(sg, jnp.asarray(dst), jnp.asarray(src))
g_ref = from_edges_host(V, dst, src, hashing=False)
qs = rng.integers(0, V, 128).astype(np.uint32)
qd = rng.integers(0, V, 128).astype(np.uint32)
got = query_edges_sharded(sg, jnp.asarray(qs), jnp.asarray(qd))
want = query_edges(g_ref, jnp.asarray(qs), jnp.asarray(qd))
assert np.array_equal(np.asarray(got), np.asarray(want))

uniq = set(zip(src.tolist(), dst.tolist()))
out_deg = np.zeros(V, np.int32)
for s, _ in uniq:
    out_deg[s] += 1
from repro.algorithms import bfs_vanilla, pagerank, wcc_labelprop_sweep
pr_sharded, _ = pagerank_sharded(sg, jnp.asarray(out_deg), max_iter=60)
pr_ref, _ = pagerank(g_ref, jnp.asarray(out_deg), max_iter=60)
assert np.allclose(np.asarray(pr_sharded), np.asarray(pr_ref), atol=1e-5)

# sharded BFS over the in-edge graph, bit-identical to the union algorithm
g_fwd = from_edges_host(V, src, dst, hashing=False)
dist_sharded, _ = bfs_sharded(sg, src=0)
dist_ref, _ = bfs_vanilla(g_fwd, src=0, edge_capacity=1 << 14, g_in=g_ref)
assert np.array_equal(np.asarray(dist_sharded), np.asarray(dist_ref))

# sharded WCC over the symmetric union, bit-identical labels
s2 = np.concatenate([src, dst])
d2 = np.concatenate([dst, src])
sg_sym = place_sg(shard_empty(V, S, capacity_slabs_per_shard=512))
sg_sym, _ = insert_edges_sharded(sg_sym, jnp.asarray(s2), jnp.asarray(d2))
lab_sharded, _ = wcc_sharded(sg_sym)
lab_ref, _ = wcc_labelprop_sweep(from_edges_host(V, s2, d2, hashing=False))
assert np.array_equal(np.asarray(lab_sharded), np.asarray(lab_ref))
print("OK sharded dynamic graph: query/pagerank/bfs/wcc match global reference")

# skewed overflow batch: every edge owned by shard 3, routed through an
# explicitly undersized cap — the grow-retry path must land them all
sk_src = (rng.integers(0, V // S, 96).astype(np.uint32) * S + 3) % V
sk_dst = rng.integers(0, V, 96).astype(np.uint32)
keep = sk_src != sk_dst
sk_src, sk_dst = sk_src[keep], sk_dst[keep]
sg_sk = place_sg(shard_empty(V, S, capacity_slabs_per_shard=256))
sg_sk, ins_sk = insert_edges_sharded(sg_sk, jnp.asarray(sk_src),
                                     jnp.asarray(sk_dst), cap=4)
assert int(ins_sk.sum()) == len(set(zip(sk_src.tolist(), sk_dst.tolist())))
assert bool(np.asarray(query_edges_sharded(
    sg_sk, jnp.asarray(sk_src), jnp.asarray(sk_dst))).all())
print("OK sharded overflow batch: undersized cap grew, no silent drops")

# ShardedGraphStore epochs on the mesh track the unsharded GraphStore
from repro.stream import GraphStore, ShardedGraphStore
ss = ShardedGraphStore.from_edges(V, S, src, dst)
for name, view in ss.views.items():
    ss._views[name] = place_sg(view)
us = GraphStore.from_edges(V, src, dst)
rng2 = np.random.default_rng(1)
for _ in range(2):
    ins2 = rng2.integers(0, V, (256, 2)).astype(np.uint32)
    ins2 = ins2[ins2[:, 0] != ins2[:, 1]]
    dels2 = np.array(sorted(uniq), np.uint32)[
        rng2.choice(len(uniq), 64, replace=False)]
    ss.apply(ins2[:, 0], ins2[:, 1], None, dels2[:, 0], dels2[:, 1])
    us.apply(ins2[:, 0], ins2[:, 1], None, dels2[:, 0], dels2[:, 1])
    uniq -= {(int(a), int(b)) for a, b in dels2}
    uniq |= {(int(a), int(b)) for a, b in ins2}
    q = rng2.integers(0, V, (256, 2)).astype(np.uint32)
    assert np.array_equal(ss.query(q[:, 0], q[:, 1]),
                          us.query(q[:, 0], q[:, 1]))
assert np.array_equal(np.asarray(ss.out_degree), np.asarray(us.out_degree))
assert ss.n_edges == us.n_edges
print("OK ShardedGraphStore epochs on the mesh track the unsharded store")

# ---------------------------------------------------------------------------
# 2b. single-program plane on the real 8-device host mesh: each epoch is ONE
#     shard_map program (on-device all-to-all routing + every view's
#     delete/insert + epoch close), pools leaf-for-leaf identical to the
#     stacked-vmap fallback, analytics bit-identical between dispatch modes
# ---------------------------------------------------------------------------
from repro.distributed.sharded_graph import place_on_mesh

sm = ShardedGraphStore.from_edges(V, S, src, dst).place_on_mesh(flat_mesh)
svf = ShardedGraphStore.from_edges(V, S, src, dst, dispatch="vmap")
assert sm._mode() == "shard_map" and svf._mode() == "vmap"
rng3 = np.random.default_rng(7)
pairs = set(zip(src.tolist(), dst.tolist()))
for ep in range(3):
    if ep == 1:
        # skewed epoch: every insert owned by shard 5
        ins3 = np.stack([(rng3.integers(0, V // S, 128) * S + 5) % V,
                         rng3.integers(0, V, 128)], 1).astype(np.uint32)
    else:
        ins3 = rng3.integers(0, V, (192, 2)).astype(np.uint32)
    ins3 = ins3[ins3[:, 0] != ins3[:, 1]]
    cur = np.array(sorted(pairs), np.uint32)
    dels3 = cur[rng3.choice(len(cur), min(48, len(cur)), replace=False)]
    sm.apply(ins3[:, 0], ins3[:, 1], None, dels3[:, 0], dels3[:, 1])
    svf.apply(ins3[:, 0], ins3[:, 1], None, dels3[:, 0], dels3[:, 1])
    pairs -= {(int(a), int(b)) for a, b in dels3}
    pairs |= {(int(a), int(b)) for a, b in ins3}
    for name in svf.views:
        got = jax.tree.leaves(sm.views[name].graphs)
        want = jax.tree.leaves(svf.views[name].graphs)
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(got, want)), (ep, name)

pr_sm, _ = pagerank_sharded(place_on_mesh(sg, flat_mesh),
                            jnp.asarray(out_deg), max_iter=60)
assert np.array_equal(np.asarray(pr_sm), np.asarray(pr_sharded))
dist_sm, _ = bfs_sharded(place_on_mesh(sg, flat_mesh), src=0)
assert np.array_equal(np.asarray(dist_sm), np.asarray(dist_sharded))
lab_sm, _ = wcc_sharded(place_on_mesh(sg_sym, flat_mesh))
assert np.array_equal(np.asarray(lab_sm), np.asarray(lab_sharded))
print("OK single-program plane: shard_map epochs + analytics "
      "bit-identical to the vmap fallback")

# ---------------------------------------------------------------------------
# 3. elastic restore: checkpoint from one mesh, restore onto another
# ---------------------------------------------------------------------------
import tempfile
from repro.checkpoint import ckpt

with tempfile.TemporaryDirectory() as td:
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
    placed = jax.device_put(tree["w"],
                            NamedSharding(mesh_a, P("data", "model")))
    ckpt.save(td, 1, {"w": placed})
    mesh_b = jax.make_mesh((2, 4), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
    shardings = {"w": NamedSharding(mesh_b, P("model", "data"))}
    restored, _ = ckpt.restore(td, tree, shardings=shardings)
    assert np.array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    assert restored["w"].sharding.mesh.shape == {"data": 2, "model": 4}
print("OK elastic restore across mesh shapes")
print("ALL MULTIDEVICE CHECKS PASSED")

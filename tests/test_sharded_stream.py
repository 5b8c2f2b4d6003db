"""Sharded stream plane: routing overflow contract, engine-backed sharded
ops, ShardedGraphStore vs the unsharded oracle, and distributed analytics
(PageRank / WCC / BFS) vs the single-graph algorithms on the unsharded
union.  Runs single-device (vmap semantics are device-count independent);
tests/multidevice_script.py repeats the core checks on a real 8-device mesh.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.algorithms import bfs_vanilla, pagerank, wcc_labelprop_sweep
from repro.core import from_edges_host, pool_edges, query_edges
from repro.distributed.sharded_graph import (apply_update_sharded,
                                             bfs_sharded,
                                             delete_edges_sharded,
                                             insert_edges_sharded,
                                             pagerank_sharded,
                                             query_edges_sharded, route_edges,
                                             routing_cap, shard_empty,
                                             shard_slice, wcc_sharded)
from repro.stream import (GraphStore, PropertyRegistry, ShardedGraphStore,
                          sharded_bfs_property, sharded_pagerank_property,
                          sharded_wcc_property)

V = 53          # deliberately V % 8 != 0: tail-padded local id spaces
S = 8


def rand_edges(rng, n, v=V):
    src = rng.integers(0, v, n).astype(np.uint32)
    dst = rng.integers(0, v, n).astype(np.uint32)
    keep = src != dst
    return src[keep], dst[keep]


def skewed_edges(rng, n, v=V, s=S, shard=0):
    """Every src owned by one shard — the bucket-overflow adversary."""
    src = (rng.integers(0, v // s, n).astype(np.uint32) * s + shard) % v
    dst = rng.integers(0, v, n).astype(np.uint32)
    keep = src != dst
    return src[keep], dst[keep]


def edge_set(g):
    view = pool_edges(g)
    m = np.asarray(view.valid)
    return set(zip(np.asarray(view.src)[m].tolist(),
                   np.asarray(view.dst)[m].astype(np.int64).tolist()))


def sharded_edge_set(sg):
    """Global (src, dst) pairs across every shard's local pool."""
    out = set()
    for k in range(sg.n_shards):
        g = shard_slice(sg, k)
        view = pool_edges(g)
        m = np.asarray(view.valid)
        gs = np.asarray(view.src)[m].astype(np.int64) * sg.n_shards + k
        out |= set(zip(gs.tolist(),
                       np.asarray(view.dst)[m].astype(np.int64).tolist()))
    return out


# ---------------------------------------------------------------------------
# routing: the overflow contract
# ---------------------------------------------------------------------------

class TestRouting:
    def test_overflow_witness_reported(self):
        """A skewed batch overflowing one shard's bucket must be reported,
        not silently masked out."""
        rng = np.random.default_rng(0)
        src, dst = skewed_edges(rng, 40)
        _, _, _, origin, over = route_edges(
            jnp.asarray(src), jnp.asarray(dst), n_shards=S, cap=4)
        assert int(over) == len(src) - 4          # true max run − cap
        assert int((np.asarray(origin) >= 0).sum()) == 4

    def test_full_batch_cap_never_overflows(self):
        rng = np.random.default_rng(1)
        src, dst = skewed_edges(rng, 32)
        _, _, _, origin, over = route_edges(
            jnp.asarray(src), jnp.asarray(dst), n_shards=S, cap=len(src))
        assert int(over) == 0
        assert int((np.asarray(origin) >= 0).sum()) == len(src)

    def test_routing_cap_is_exact_max_run(self):
        src = np.array([0, 8, 16, 1, 9], np.uint32)   # 3 on shard 0, 2 on 1
        assert routing_cap(src, S) == 4               # pow2(3)
        assert routing_cap(np.array([], np.uint32), S) == 1

    @pytest.mark.parametrize("cap", [0, 1, 2])
    def test_undersized_cap_grows_no_silent_drop(self, cap):
        """insert/query through an explicitly undersized cap must still land
        every edge (grow+retry), and report them present — the old path
        reported dropped edges as plain False."""
        rng = np.random.default_rng(2)
        src, dst = skewed_edges(rng, 48)
        sg = shard_empty(V, S, capacity_slabs_per_shard=256)
        sg, ins = insert_edges_sharded(sg, jnp.asarray(src),
                                       jnp.asarray(dst), cap=cap)
        g = from_edges_host(V, src, dst, hashing=False)
        assert int(ins.sum()) == int(g.n_edges)
        got = query_edges_sharded(sg, jnp.asarray(src), jnp.asarray(dst),
                                  cap=cap)
        assert bool(np.asarray(got).all())
        assert sharded_edge_set(sg) == edge_set(g)

    def test_cap_none_defaults_to_full_batch(self):
        rng = np.random.default_rng(3)
        src, dst = skewed_edges(rng, 24)
        sg = shard_empty(V, S, capacity_slabs_per_shard=128)
        sg, ins = insert_edges_sharded(sg, jnp.asarray(src),
                                       jnp.asarray(dst), cap=None)
        assert int(ins.sum()) == len(set(zip(src.tolist(), dst.tolist())))

    def test_empty_batches_are_noops(self):
        e = jnp.zeros((0,), jnp.uint32)
        sg = shard_empty(V, S, capacity_slabs_per_shard=64)
        sg, ins = insert_edges_sharded(sg, e, e)
        assert ins.shape == (0,)
        sg, dele = delete_edges_sharded(sg, e, e)
        assert dele.shape == (0,)
        assert query_edges_sharded(sg, e, e).shape == (0,)
        sg2, im, dm = apply_update_sharded(sg, e, e, None, e, e)
        assert im is None and dm is None


# ---------------------------------------------------------------------------
# engine-backed sharded ops vs the single-graph oracle
# ---------------------------------------------------------------------------

class TestShardedOps:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_mixed_stream_matches_unsharded(self, seed):
        rng = np.random.default_rng(seed)
        sg = shard_empty(V, S, capacity_slabs_per_shard=512)
        oracle = set()
        for _ in range(3):
            ins_s, ins_d = rand_edges(rng, 40)
            sg, _ = insert_edges_sharded(sg, jnp.asarray(ins_s),
                                         jnp.asarray(ins_d))
            oracle |= set(zip(ins_s.tolist(), ins_d.tolist()))
            if oracle:
                pres = np.array(sorted(oracle), np.uint32)
                k = min(8, len(pres))
                dels = pres[rng.choice(len(pres), k, replace=False)]
                sg, dele = delete_edges_sharded(sg, jnp.asarray(dels[:, 0]),
                                                jnp.asarray(dels[:, 1]))
                assert bool(np.asarray(dele).all())
                oracle -= {(int(a), int(b)) for a, b in dels}
            assert sharded_edge_set(sg) == oracle
            qs, qd = rand_edges(rng, 64)
            got = query_edges_sharded(sg, jnp.asarray(qs), jnp.asarray(qd))
            want = np.array([(int(a), int(b)) in oracle
                             for a, b in zip(qs, qd)])
            assert np.array_equal(np.asarray(got), want)

    def test_apply_update_sharded_fused_epoch(self):
        rng = np.random.default_rng(7)
        src, dst = rand_edges(rng, 60)
        sg = shard_empty(V, S, capacity_slabs_per_shard=512)
        sg, _ = insert_edges_sharded(sg, jnp.asarray(src), jnp.asarray(dst))
        oracle = set(zip(src.tolist(), dst.tolist()))
        pres = np.array(sorted(oracle), np.uint32)
        dels = pres[:6]
        ins_s, ins_d = rand_edges(rng, 20)
        sg, ins_m, del_m = apply_update_sharded(
            sg, jnp.asarray(ins_s), jnp.asarray(ins_d), None,
            jnp.asarray(dels[:, 0]), jnp.asarray(dels[:, 1]))
        oracle -= {(int(a), int(b)) for a, b in dels}
        oracle |= set(zip(ins_s.tolist(), ins_d.tolist()))
        assert sharded_edge_set(sg) == oracle
        assert bool(np.asarray(del_m).all())


# ---------------------------------------------------------------------------
# distributed analytics vs the unsharded union
# ---------------------------------------------------------------------------

class TestShardedAnalytics:
    def _build(self, seed=4, n=250):
        rng = np.random.default_rng(seed)
        src, dst = rand_edges(rng, n)
        uniq = sorted(set(zip(src.tolist(), dst.tolist())))
        o = np.array(uniq, np.int64)
        return o[:, 0].astype(np.uint32), o[:, 1].astype(np.uint32)

    def test_pagerank_sharded_on_sweep_engine(self):
        src, dst = self._build()
        out_deg = np.bincount(src.astype(np.int64), minlength=V) \
            .astype(np.int32)
        g_in = from_edges_host(V, dst, src, hashing=False)
        sg = shard_empty(V, S, capacity_slabs_per_shard=512)
        sg, _ = insert_edges_sharded(sg, jnp.asarray(dst), jnp.asarray(src))
        got, _ = pagerank_sharded(sg, jnp.asarray(out_deg), max_iter=80)
        want, _ = pagerank(g_in, jnp.asarray(out_deg), max_iter=80)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_wcc_sharded_bit_identical(self):
        src, dst = self._build(seed=5)
        s2 = np.concatenate([src, dst])
        d2 = np.concatenate([dst, src])
        g_sym = from_edges_host(V, s2, d2, hashing=False)
        sg = shard_empty(V, S, capacity_slabs_per_shard=1024)
        sg, _ = insert_edges_sharded(sg, jnp.asarray(s2), jnp.asarray(d2))
        got, _ = wcc_sharded(sg)
        want, _ = wcc_labelprop_sweep(g_sym)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_bfs_sharded_bit_identical(self):
        src, dst = self._build(seed=6, n=180)
        g = from_edges_host(V, src, dst, hashing=False)
        g_in = from_edges_host(V, dst, src, hashing=False)
        sg = shard_empty(V, S, capacity_slabs_per_shard=512)
        sg, _ = insert_edges_sharded(sg, jnp.asarray(dst), jnp.asarray(src))
        got, _ = bfs_sharded(sg, src=0)
        want, _ = bfs_vanilla(g, src=0, edge_capacity=8192, g_in=g_in)
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# ShardedGraphStore vs GraphStore, leaf-for-leaf oracle streams
# ---------------------------------------------------------------------------

class TestShardedStore:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_randomized_epochs_match_unsharded_store(self, seed):
        """Every sharded view's global edge set, out-degrees, n_edges, and
        query results track the unsharded GraphStore epoch for epoch —
        including the tail-padded V % n_shards != 0 id space."""
        rng = np.random.default_rng(seed)
        src, dst = rand_edges(rng, 70)
        ss = ShardedGraphStore.from_edges(V, S, src, dst)
        us = GraphStore.from_edges(V, src, dst)
        oracle = set(zip(src.tolist(), dst.tolist()))
        assert ss.n_edges == us.n_edges == len(oracle)

        for epoch in range(4):
            ins_s, ins_d = rand_edges(rng, 14)
            pres = np.array(sorted(oracle), np.uint32)
            k = min(5, len(pres))
            dels = pres[rng.choice(len(pres), k, replace=False)]
            ss.apply(ins_s, ins_d, None, dels[:, 0], dels[:, 1])
            us.apply(ins_s, ins_d, None, dels[:, 0], dels[:, 1])
            oracle -= {(int(a), int(b)) for a, b in dels}
            oracle |= set(zip(ins_s.tolist(), ins_d.tolist()))
            assert ss.version == us.version == epoch + 1
            for name in ("forward", "transpose", "symmetric"):
                assert sharded_edge_set(ss.views[name]) == \
                    edge_set(us.views[name]), (name, epoch)
            assert np.array_equal(np.asarray(ss.out_degree),
                                  np.asarray(us.out_degree))
            assert ss.n_edges == us.n_edges
            q = rng.integers(0, V, (64, 2)).astype(np.uint32)
            assert np.array_equal(ss.query(q[:, 0], q[:, 1]),
                                  us.query(q[:, 0], q[:, 1]))

    def test_skewed_overflow_batch_no_silent_drop(self):
        """A batch that lands entirely on ONE shard (the route_edges
        silent-drop adversary) must apply completely through the store."""
        rng = np.random.default_rng(9)
        ss = ShardedGraphStore.from_edges(V, S, [], [])
        us = GraphStore.from_edges(V, [], [])
        src, dst = skewed_edges(rng, 64)
        b1 = ss.apply(ins_src=src, ins_dst=dst)
        b2 = us.apply(ins_src=src, ins_dst=dst)
        assert b1.n_inserted == b2.n_inserted > 0
        assert sharded_edge_set(ss.forward) == edge_set(us.forward)
        assert bool(np.asarray(ss.query(src, dst)).all())

    def test_weighted_sharded_store(self):
        ss = ShardedGraphStore.from_edges(V, S, [0, 1], [1, 2], [2.5, 0.5])
        ss.apply(ins_src=[8], ins_dst=[3])      # defaults to weight 1.0
        got = {}
        for k in range(S):
            g = shard_slice(ss.forward, k)
            view = pool_edges(g)
            m = np.asarray(view.valid)
            gs = np.asarray(view.src)[m].astype(np.int64) * S + k
            for a, b, w in zip(gs.tolist(),
                               np.asarray(view.dst)[m].tolist(),
                               np.asarray(view.weight)[m].tolist()):
                got[(a, b)] = w
        assert got == {(0, 1): 2.5, (1, 2): 0.5, (8, 3): 1.0}

    def test_pipeline_requests_including_neighbors(self):
        """The full RequestPipeline surface works on the sharded store —
        including NeighborsQuery (globalised per-shard chain walks)."""
        from repro.stream import (MembershipQuery, NeighborsQuery,
                                  RequestPipeline, UpdateBatch)
        ss = ShardedGraphStore.from_edges(V, S, [0, 0, 1], [1, 2, 3])
        resps = RequestPipeline(ss).run([
            UpdateBatch(ins_src=[2], ins_dst=[4]),
            MembershipQuery(src=[0, 0], dst=[1, 5]),
            NeighborsQuery(vertices=[0, 2]),
        ])
        assert resps[1].payload["found"].tolist() == [True, False]
        got = set(zip(resps[2].payload["src"].tolist(),
                      resps[2].payload["dst"].tolist()))
        assert got == {(0, 1), (0, 2), (2, 4)}
        assert not resps[2].payload["overflow"]

    def test_properties_track_recompute(self):
        """Registered sharded properties (lazy) equal fresh recomputes on
        the live store after mixed epochs."""
        rng = np.random.default_rng(11)
        src, dst = rand_edges(rng, 80)
        ss = ShardedGraphStore.from_edges(V, S, src, dst)
        reg = PropertyRegistry(ss)
        reg.register(sharded_pagerank_property())
        reg.register(sharded_bfs_property(0))
        reg.register(sharded_wcc_property())
        oracle = set(zip(src.tolist(), dst.tolist()))

        for _ in range(2):
            ins_s, ins_d = rand_edges(rng, 12)
            pres = np.array(sorted(oracle), np.uint32)
            dels = pres[rng.choice(len(pres), 4, replace=False)]
            ss.apply(ins_s, ins_d, None, dels[:, 0], dels[:, 1])
            oracle -= {(int(a), int(b)) for a, b in dels}
            oracle |= set(zip(ins_s.tolist(), ins_d.tolist()))

            o = np.array(sorted(oracle), np.int64)
            g_f = from_edges_host(V, o[:, 0], o[:, 1], hashing=False)
            g_in = from_edges_host(V, o[:, 1], o[:, 0], hashing=False)
            g_sym = from_edges_host(
                V, np.concatenate([o[:, 0], o[:, 1]]),
                np.concatenate([o[:, 1], o[:, 0]]), hashing=False)

            want_pr, _ = pagerank(g_in, ss.out_degree)
            np.testing.assert_allclose(np.asarray(reg.read("pagerank")),
                                       np.asarray(want_pr), atol=5e-4)
            want_lab, _ = wcc_labelprop_sweep(g_sym)
            assert np.array_equal(np.asarray(reg.read("wcc")),
                                  np.asarray(want_lab))
            want_dist, _ = bfs_vanilla(g_f, src=0, edge_capacity=8192,
                                       g_in=g_in)
            assert np.array_equal(np.asarray(reg.read("bfs_0")),
                                  np.asarray(want_dist))


# ---------------------------------------------------------------------------
# single-program dispatch on a 1-device mesh (the full shard_map epoch
# program — all-to-all routing, collective exchanges, donation — runs fine
# at S=1; tests/shard_map_script.py repeats this at S=8 in a subprocess)
# ---------------------------------------------------------------------------
class TestShardMapDispatchS1:
    def test_epochs_and_analytics_identical_to_vmap(self):
        import jax
        from repro.distributed.sharded_graph import shard_mesh
        rng = np.random.default_rng(11)
        src, dst = rand_edges(rng, 160)
        mesh = shard_mesh(1)
        sv = ShardedGraphStore.from_edges(V, 1, src, dst, dispatch="vmap")
        sm = ShardedGraphStore.from_edges(V, 1, src, dst) \
            .place_on_mesh(mesh)
        assert sm._mode() == "shard_map" and sv._mode() == "vmap"

        oracle = set(zip(src.tolist(), dst.tolist()))
        for _ in range(3):
            ins_s, ins_d = rand_edges(rng, 48)
            pres = np.array(sorted(oracle), np.uint32)
            dels = pres[rng.choice(len(pres), 12, replace=False)]
            bv = sv.apply(ins_s, ins_d, None, dels[:, 0], dels[:, 1])
            bm = sm.apply(ins_s, ins_d, None, dels[:, 0], dels[:, 1])
            assert bv.n_inserted == bm.n_inserted
            assert bv.n_deleted == bm.n_deleted
            oracle -= {(int(a), int(b)) for a, b in dels}
            oracle |= set(zip(ins_s.tolist(), ins_d.tolist()))
            for name in sv.views:
                got = jax.tree.leaves(sm.views[name].graphs)
                want = jax.tree.leaves(sv.views[name].graphs)
                assert all(np.array_equal(np.asarray(x), np.asarray(y))
                           for x, y in zip(got, want)), name

        reg_m = PropertyRegistry(sm)
        reg_v = PropertyRegistry(sv)
        for reg in (reg_m, reg_v):
            reg.register(sharded_pagerank_property(max_iter=30))
            reg.register(sharded_wcc_property())
        assert np.array_equal(np.asarray(reg_m.read("pagerank")),
                              np.asarray(reg_v.read("pagerank")))
        assert np.array_equal(np.asarray(reg_m.read("wcc")),
                              np.asarray(reg_v.read("wcc")))

    def test_place_on_mesh_needs_auto_axes(self):
        import jax
        rng = np.random.default_rng(13)
        src, dst = rand_edges(rng, 40)
        st = ShardedGraphStore.from_edges(V, 1, src, dst)
        explicit = jax.make_mesh((1,), ("shard",),
                                 axis_types=(jax.sharding.AxisType.Explicit,))
        with pytest.raises(ValueError, match="Auto-typed mesh"):
            st.place_on_mesh(explicit)

    def test_dispatch_mode_validation(self):
        rng = np.random.default_rng(12)
        src, dst = rand_edges(rng, 40)
        st = ShardedGraphStore.from_edges(V, 1, src, dst,
                                          dispatch="shard_map")
        with pytest.raises(ValueError, match="mesh-placed"):
            st.apply(src[:4], dst[:4])

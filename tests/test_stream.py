"""`repro.stream` subsystem tests: view consistency against an edge-set
oracle rebuild, incremental properties against static recompute, update
coalescing semantics, the request pipeline, and checkpoint round trips.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.algorithms import (bfs_stream_property, bfs_tree_static, pagerank,
                              pagerank_stream_property, sssp_static,
                              sssp_stream_property, wcc_static,
                              wcc_stream_property)
from repro.core import from_edges_host, pool_edges
from repro.stream import (GraphStore, MembershipQuery, NeighborsQuery,
                          PropertyRead, PropertyRegistry, PropertySpec,
                          RequestPipeline, UpdateBatch, coalesce_updates,
                          dedup_pairs)

V = 24
CAP = 4096


def edge_set(g):
    view = pool_edges(g)
    m = np.asarray(view.valid)
    return set(zip(np.asarray(view.src)[m].tolist(),
                   np.asarray(view.dst)[m].astype(np.int64).tolist()))


def weighted_edge_set(g):
    view = pool_edges(g)
    m = np.asarray(view.valid)
    return set(zip(np.asarray(view.src)[m].tolist(),
                   np.asarray(view.dst)[m].astype(np.int64).tolist(),
                   np.asarray(view.weight)[m].tolist()))


def random_epoch(rng, oracle, *, n_ins=12, n_del=6):
    """An insert batch + a delete batch (mix of present and absent pairs)."""
    ins = rng.integers(0, V, (n_ins, 2)).astype(np.uint32)
    ins = ins[ins[:, 0] != ins[:, 1]]
    present = np.array(sorted(oracle), np.uint32) if oracle else \
        np.zeros((0, 2), np.uint32)
    k = min(n_del // 2, len(present))
    hits = present[rng.choice(len(present), k, replace=False)] if k else \
        np.zeros((0, 2), np.uint32)
    misses = rng.integers(0, V, (n_del - k, 2)).astype(np.uint32)
    dels = np.concatenate([hits, misses]) if len(misses) else hits
    return ins, dels


def apply_to_oracle(oracle, ins, dels):
    """Store contract: deletes first, then inserts."""
    oracle -= {(int(s), int(d)) for s, d in dels}
    oracle |= {(int(s), int(d)) for s, d in ins if s != d}


class TestStoreViews:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_randomized_epochs_match_oracle_rebuild(self, seed):
        """Every view stays identical (edge set + degrees + counts) to a
        fresh from_edges_host rebuild from the edge-set oracle."""
        rng = np.random.default_rng(seed)
        src, dst = rng.integers(0, V, 60).astype(np.uint32), \
            rng.integers(0, V, 60).astype(np.uint32)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        store = GraphStore.from_edges(V, src, dst)
        oracle = set(zip(src.tolist(), dst.tolist()))

        for epoch in range(4):
            ins, dels = random_epoch(rng, oracle)
            store.apply(ins[:, 0], ins[:, 1], None,
                        dels[:, 0] if len(dels) else (),
                        dels[:, 1] if len(dels) else ())
            apply_to_oracle(oracle, ins, dels)
            assert store.version == epoch + 1

            o = np.array(sorted(oracle), np.int64) if oracle else \
                np.zeros((0, 2), np.int64)
            rebuilds = {
                "forward": from_edges_host(V, o[:, 0], o[:, 1]),
                "transpose": from_edges_host(V, o[:, 1], o[:, 0]),
                "symmetric": from_edges_host(
                    V, np.concatenate([o[:, 0], o[:, 1]]),
                    np.concatenate([o[:, 1], o[:, 0]])),
            }
            for name, fresh in rebuilds.items():
                live = store.views[name]
                assert edge_set(live) == edge_set(fresh), (name, epoch)
                assert np.array_equal(np.asarray(live.degree),
                                      np.asarray(fresh.degree)), (name, epoch)
                assert int(live.n_edges) == int(fresh.n_edges), (name, epoch)

    def test_symmetric_survives_one_direction_delete(self):
        """Deleting (a,b) keeps (a,b)/(b,a) in the symmetric union while the
        reverse edge (b,a) is still present."""
        store = GraphStore.from_edges(4, [0, 1], [1, 0])
        store.apply(del_src=[0], del_dst=[1])
        assert edge_set(store.forward) == {(1, 0)}
        assert edge_set(store.symmetric) == {(0, 1), (1, 0)}
        store.apply(del_src=[1], del_dst=[0])
        assert edge_set(store.forward) == set()
        assert edge_set(store.symmetric) == set()

    def test_epochs_close_and_degrees_stay_on_device(self):
        store = GraphStore.from_edges(V, [0, 1], [1, 2])
        store.apply(ins_src=[2, 3], ins_dst=[3, 4])
        for g in store.views.values():
            assert not bool(np.asarray(g.upd_flag).any())
            assert int(g.epoch_next_free) == int(g.next_free)
        assert isinstance(store.out_degree, jnp.ndarray)
        deg = np.zeros(V, np.int32)
        deg[[0, 1, 2, 3]] = 1
        assert np.array_equal(np.asarray(store.out_degree), deg)

    def test_weighted_insert_defaults_and_carries_weights(self):
        store = GraphStore.from_edges(4, [0], [1], [2.5])
        store.apply(ins_src=[1, 2], ins_dst=[2, 3], ins_w=[0.5, 1.5])
        store.apply(ins_src=[3], ins_dst=[0])  # defaults to weight 1.0
        assert weighted_edge_set(store.forward) == \
            {(0, 1, 2.5), (1, 2, 0.5), (2, 3, 1.5), (3, 0, 1.0)}
        assert weighted_edge_set(store.transpose) == \
            {(1, 0, 2.5), (2, 1, 0.5), (3, 2, 1.5), (0, 3, 1.0)}

    def test_dedup_pairs_keeps_first_weight(self):
        s, d, w = dedup_pairs([1, 1, 2], [2, 2, 3], [5.0, 9.0, 1.0])
        assert s.tolist() == [1, 2] and d.tolist() == [2, 3]
        assert w.tolist() == [5.0, 1.0]


class TestProperties:
    @pytest.mark.parametrize("policy,weighted", [("lazy", False),
                                                 ("eager", False),
                                                 ("lazy", True),
                                                 ("eager", True)])
    def test_match_static_recompute_across_epochs(self, policy, weighted):
        """After every mixed epoch, each registered property equals a fresh
        static recompute on the live store.  BFS rides unweighted stores
        (unit weights), SSSP weighted ones."""
        rng = np.random.default_rng(5)
        src, dst = rng.integers(0, V, 80).astype(np.uint32), \
            rng.integers(0, V, 80).astype(np.uint32)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        w = rng.uniform(0.5, 3.0, len(src)).astype(np.float32) if weighted \
            else None
        store = GraphStore.from_edges(V, src, dst, w)
        oracle = set(zip(src.tolist(), dst.tolist()))

        registry = PropertyRegistry(store)
        registry.register(pagerank_stream_property(), policy=policy)
        tree_name = "sssp_0" if weighted else "bfs_0"
        registry.register(
            (sssp_stream_property if weighted else bfs_stream_property)(
                0, edge_capacity=CAP), policy=policy)
        registry.register(wcc_stream_property(), policy=policy)

        for _ in range(3):
            ins, dels = random_epoch(rng, oracle, n_ins=10, n_del=4)
            iw = rng.uniform(0.5, 3.0, len(ins)).astype(np.float32) \
                if weighted else None
            store.apply(ins[:, 0], ins[:, 1], iw,
                        dels[:, 0] if len(dels) else (),
                        dels[:, 1] if len(dels) else ())
            apply_to_oracle(oracle, ins, dels)

            tree_got = registry.read(tree_name)
            static = sssp_static if weighted else bfs_tree_static
            tree_want, _ = static(store.forward, 0, edge_capacity=CAP,
                                  g_in=store.transpose)
            assert np.array_equal(np.asarray(tree_got.dist),
                                  np.asarray(tree_want.dist))
            assert np.array_equal(np.asarray(tree_got.parent),
                                  np.asarray(tree_want.parent))

            assert np.array_equal(np.asarray(registry.read("wcc")),
                                  np.asarray(wcc_static(store.forward)))

            pr_want, _ = pagerank(store.transpose, store.out_degree)
            assert np.allclose(np.asarray(registry.read("pagerank")),
                               np.asarray(pr_want), atol=5e-4)

    def test_lazy_stays_stale_until_read(self):
        store = GraphStore.from_edges(V, [0, 1], [1, 2])
        registry = PropertyRegistry(store)
        registry.register(wcc_stream_property(), policy="lazy")
        registry.register(bfs_stream_property(0, edge_capacity=256),
                          policy="eager")
        store.apply(ins_src=[2], ins_dst=[3])
        status = registry.status()
        assert status["wcc"]["stale"] and not status["bfs_0"]["stale"]
        registry.read("wcc")
        assert not registry.status()["wcc"]["stale"]

    def test_lag_past_max_replay_refreshes(self):
        """A lazy property replays a lag of up to ``max_replay`` epochs and
        refreshes past it; BFS and WCC declare one."""
        calls = []

        def note(kind):
            return lambda store, *_: calls.append(kind) or store.version

        store = GraphStore.from_edges(V, [0, 1], [1, 2])
        registry = PropertyRegistry(store)
        registry.register(PropertySpec(
            "version", init=note("init"), on_batch=note("replay"),
            refresh=note("refresh"), max_replay=1), policy="lazy")
        store.apply(ins_src=[2], ins_dst=[3])
        assert registry.read("version") == store.version
        for k in range(3):
            store.apply(ins_src=[3 + k], ins_dst=[4 + k])
        assert registry.read("version") == store.version
        assert calls == ["init", "replay", "refresh"]
        assert bfs_stream_property(0, edge_capacity=CAP).max_replay == 1
        assert wcc_stream_property().max_replay == 1

    def test_sweep_rows_bound_is_bit_identical(self):
        """``GraphStore.sweep_rows`` cuts the sweeps to the allocated slab
        prefix; PageRank and the BFS tree come out bit-identical."""
        n = 600
        rng = np.random.default_rng(3)
        src, dst = rng.integers(0, n, (2, 3000)).astype(np.uint32)
        keep = src != dst
        store = GraphStore.from_edges(n, src[keep], dst[keep])
        rows = store.sweep_rows()
        assert rows < store.transpose.capacity_slabs
        assert rows >= int(store.transpose.next_free)
        for got, want in (
                (pagerank(store.transpose, store.out_degree, rows=rows)[0],
                 pagerank(store.transpose, store.out_degree)[0]),
                *zip(bfs_tree_static(store.forward, 0, edge_capacity=CAP,
                                     g_in=store.transpose, rows=rows)[0],
                     bfs_tree_static(store.forward, 0, edge_capacity=CAP,
                                     g_in=store.transpose)[0])):
            assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_truncated_log_falls_back_to_refresh(self):
        store = GraphStore.from_edges(V, [0, 1], [1, 2], log_capacity=1)
        registry = PropertyRegistry(store)
        registry.register(wcc_stream_property(), policy="lazy")
        for k in range(3):  # 3 epochs through a 1-deep log
            store.apply(ins_src=[2 + k], ins_dst=[3 + k])
        assert store.batches_since(0) is None
        assert np.array_equal(np.asarray(registry.read("wcc")),
                              np.asarray(wcc_static(store.forward)))


class TestRequests:
    def test_coalesce_last_op_wins(self):
        net = coalesce_updates([
            UpdateBatch(ins_src=[0], ins_dst=[1]),
            UpdateBatch(del_src=[0, 2], del_dst=[1, 3]),
            UpdateBatch(ins_src=[2], ins_dst=[3]),
        ])
        # (0,1): insert then delete -> net delete.  (2,3): delete then
        # re-insert -> insert, AND delete-first so a live edge's weight
        # cannot survive the re-insert.
        assert list(zip(net.ins_src.tolist(), net.ins_dst.tolist())) == \
            [(2, 3)]
        assert set(zip(net.del_src.tolist(), net.del_dst.tolist())) == \
            {(0, 1), (2, 3)}

    def test_within_batch_insert_wins_over_delete(self):
        # store contract: deletes precede inserts inside one batch, so a
        # pair with both ops nets to delete-then-reinsert (ends present)
        net = coalesce_updates([UpdateBatch(ins_src=[5], ins_dst=[6],
                                            del_src=[5], del_dst=[6])])
        assert net.ins_src.tolist() == [5]
        assert net.del_src.tolist() == [5]

    def test_coalesced_reinsert_updates_weight(self):
        """Delete-then-reinsert across coalesced batches must land the new
        weight, not be rejected against the still-present edge."""
        for coalesce in (False, True):
            store = GraphStore.from_edges(4, [0], [1], [5.0])
            RequestPipeline(store, coalesce=coalesce).run([
                UpdateBatch(del_src=[0], del_dst=[1]),
                UpdateBatch(ins_src=[0], ins_dst=[1], ins_w=[9.0]),
            ])
            assert weighted_edge_set(store.forward) == {(0, 1, 9.0)}, coalesce
            assert weighted_edge_set(store.transpose) == {(1, 0, 9.0)}

    def test_coalesced_pipeline_matches_sequential(self):
        rng = np.random.default_rng(9)
        src, dst = rng.integers(0, V, 40).astype(np.uint32), \
            rng.integers(0, V, 40).astype(np.uint32)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        batches = [
            UpdateBatch(ins_src=[1, 2], ins_dst=[3, 4]),
            UpdateBatch(del_src=[1], del_dst=[3]),
            UpdateBatch(ins_src=[1, 5], ins_dst=[3, 6],
                        del_src=[2], del_dst=[4]),
        ]
        s1 = GraphStore.from_edges(V, src, dst)
        RequestPipeline(s1, coalesce=True).run(batches)
        s2 = GraphStore.from_edges(V, src, dst)
        RequestPipeline(s2, coalesce=False).run(batches)
        assert s1.version == 1 and s2.version == 3
        assert edge_set(s1.forward) == edge_set(s2.forward)
        assert edge_set(s1.symmetric) == edge_set(s2.symmetric)

    def test_pipeline_batched_membership_and_neighbors(self):
        store = GraphStore.from_edges(V, [0, 0, 1], [1, 2, 3])
        resps = RequestPipeline(store).run([
            MembershipQuery(src=[0, 0], dst=[1, 5]),
            MembershipQuery(src=[1], dst=[3]),
            NeighborsQuery(vertices=[0]),
        ])
        assert resps[0].payload["found"].tolist() == [True, False]
        assert resps[0].payload["merged"] == 2
        assert resps[1].payload["found"].tolist() == [True]
        assert set(resps[2].payload["dst"].tolist()) == {1, 2}

    def test_property_read_through_pipeline(self):
        store = GraphStore.from_edges(V, [0, 1], [1, 2])
        registry = PropertyRegistry(store)
        registry.register(wcc_stream_property())
        pipe = RequestPipeline(store, registry)
        resp = pipe.run([UpdateBatch(ins_src=[2], ins_dst=[3]),
                         PropertyRead("wcc")])[1]
        assert resp.kind == "property" and resp.version == 1
        assert np.array_equal(np.asarray(resp.payload["value"]),
                              np.asarray(wcc_static(store.forward)))


class TestCheckpoint:
    def test_roundtrip_serves_identical_results(self, tmp_path):
        rng = np.random.default_rng(11)
        src, dst = rng.integers(0, V, 70).astype(np.uint32), \
            rng.integers(0, V, 70).astype(np.uint32)
        keep = src != dst
        src, dst = src[keep], dst[keep]
        store = GraphStore.from_edges(V, src, dst)
        registry = PropertyRegistry(store)
        registry.register(pagerank_stream_property())
        registry.register(bfs_stream_property(0, edge_capacity=CAP))
        registry.register(wcc_stream_property())
        store.apply(ins_src=[1, 2], ins_dst=[5, 6], del_src=src[:5],
                    del_dst=dst[:5])
        for name in registry.names():
            registry.read(name)

        store.save(tmp_path, registry=registry)
        specs = [pagerank_stream_property(),
                 bfs_stream_property(0, edge_capacity=CAP),
                 wcc_stream_property()]
        store2, registry2 = GraphStore.restore(tmp_path, specs=specs)

        assert store2.version == store.version == 1
        assert store2.weighted == store.weighted
        for name in ("forward", "transpose", "symmetric"):
            assert edge_set(store2.views[name]) == \
                edge_set(store.views[name]), name

        # identical query results from the restored store
        q = rng.integers(0, V, (64, 2)).astype(np.uint32)
        assert np.array_equal(store.query(q[:, 0], q[:, 1]),
                              store2.query(q[:, 0], q[:, 1]))
        for name in registry.names():
            a, b = registry.read(name), registry2.read(name)
            for la, lb in zip(np.asarray(a).reshape(-1, V) if not
                              hasattr(a, "dist") else
                              (np.asarray(a.dist), np.asarray(a.parent)),
                              np.asarray(b).reshape(-1, V) if not
                              hasattr(b, "dist") else
                              (np.asarray(b.dist), np.asarray(b.parent))):
                assert np.array_equal(la, lb), name

        # the restored store keeps serving: same epoch -> same state
        ins = np.array([[3, 7], [7, 9]], np.uint32)
        store.apply(ins[:, 0], ins[:, 1])
        store2.apply(ins[:, 0], ins[:, 1])
        assert edge_set(store.forward) == edge_set(store2.forward)
        assert np.array_equal(np.asarray(registry.read("wcc")),
                              np.asarray(registry2.read("wcc")))

    def test_restore_requires_specs_for_saved_props(self, tmp_path):
        store = GraphStore.from_edges(V, [0], [1])
        registry = PropertyRegistry(store)
        registry.register(wcc_stream_property())
        store.save(tmp_path, registry=registry)
        with pytest.raises(KeyError):
            GraphStore.restore(tmp_path, specs=())

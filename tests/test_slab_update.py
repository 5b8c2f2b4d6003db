"""Slab-update engine tests (kernels/slab_update, DESIGN.md §6).

Four planes of coverage:

* bit-identity — every engine impl ("jnp" run-local, "pallas" interpret)
  must reproduce the ``ref.py`` oracle's output pytree *exactly* across
  randomized mixed insert/delete/query epochs (the acceptance contract);
* semantics — a randomized property test pits the engine against a host
  ``set[(src, dst)]`` oracle across mixed epochs, chained overflow slabs,
  tombstones, and deleted-then-reinserted pairs;
* the query validity fix — sentinel (EMPTY/TOMBSTONE/INVALID) dst returns
  False instead of probing with a garbage key;
* the update-plane plumbing — fused ``apply_update``, the stacked
  ``update_views`` dispatch, power-of-two ``ensure_capacity`` quantization,
  and the one-host-dedup-per-batch contract of ``GraphStore.apply``.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core import (EMPTY_KEY, INVALID_VERTEX, SLAB_WIDTH, TOMBSTONE_KEY,
                        apply_update, delete_edges, empty, ensure_capacity,
                        from_edges_host, insert_edges, next_pow2, query_edges,
                        update_slab_pointers, update_views)
from repro.core.worklist import pool_edges
from repro.kernels.slab_update import (delete_edges_ref, insert_edges_ref,
                                       query_edges_ref)

ENGINE_IMPLS = ["jnp", "pallas"]


def pad(arr, n, fill=0xFFFFFFFF):
    a = np.full(n, fill, dtype=np.uint32)
    a[:len(arr)] = arr
    return jnp.asarray(a)


def impl_kw(impl):
    # tiny tiles exercise multi-tile grids even on small test batches;
    # use_commit_kernel keeps the opt-in aliased commit pass validated
    return (dict(impl="pallas", interpret=True, queries_per_tile=8,
                 use_commit_kernel=True)
            if impl == "pallas" else dict(impl=impl))


def tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def edges_in_graph(g):
    view = pool_edges(g)
    src = np.asarray(view.src)[np.asarray(view.valid)]
    dst = np.asarray(view.dst)[np.asarray(view.valid)]
    return set(zip(src.tolist(), dst.astype(np.int64).tolist()))


# ---------------------------------------------------------------------------
# bit-identity vs the whole-pool oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ENGINE_IMPLS)
@pytest.mark.parametrize("weighted", [False, True])
def test_engine_bit_identical_to_oracle(impl, weighted):
    """Engine and oracle graphs stay leaf-for-leaf identical across
    randomized mixed epochs (inserts, deletes, queries, epoch closes)."""
    rng = np.random.default_rng(7)
    V = 24
    kw = impl_kw(impl)
    steps = 4 if impl == "pallas" else 10
    ge = empty(V, np.full(V, 2, np.int32), 512, weighted=weighted)
    go = empty(V, np.full(V, 2, np.int32), 512, weighted=weighted)
    for step in range(steps):
        B = int(rng.integers(2, 17))
        s = rng.integers(0, V, B).astype(np.uint32)
        d = rng.integers(0, V, B).astype(np.uint32)
        w = (jnp.asarray(rng.uniform(0, 4, B).astype(np.float32))
             if weighted else None)
        ge, mi = insert_edges(ge, pad(s, B), pad(d, B), w, **kw)
        go, mo = insert_edges_ref(go, pad(s, B), pad(d, B), w)
        assert np.array_equal(np.asarray(mi), np.asarray(mo))
        assert tree_equal(ge, go), f"insert step {step}"

        ds = rng.integers(0, V, 8).astype(np.uint32)
        dd = rng.integers(0, V, 8).astype(np.uint32)
        ge, mi = delete_edges(ge, pad(ds, 8), pad(dd, 8), **kw)
        go, mo = delete_edges_ref(go, pad(ds, 8), pad(dd, 8))
        assert np.array_equal(np.asarray(mi), np.asarray(mo))
        assert tree_equal(ge, go), f"delete step {step}"

        q = query_edges(ge, pad(s, B), pad(d, B), **kw)
        qo = query_edges_ref(go, pad(s, B), pad(d, B))
        assert np.array_equal(np.asarray(q), np.asarray(qo))

        if step % 3 == 2:
            ge = update_slab_pointers(ge)
            go = update_slab_pointers(go)


@pytest.mark.parametrize("impl", ENGINE_IMPLS)
def test_engine_overflow_chains_bit_identical(impl):
    """Dense same-bucket inserts force multi-slab overflow chains; the
    engine's run-local chaining must equal the oracle's per-bucket form."""
    V = 400
    kw = impl_kw(impl)
    ge = empty(V, np.ones(V, np.int32), 1024)
    go = empty(V, np.ones(V, np.int32), 1024)
    n = 2 * SLAB_WIDTH + 37                # three slabs for vertex 0's chain
    s = [0] * n + [1] * 5
    d = list(range(1, n + 1)) + list(range(10, 15))
    B = 512
    ge, mi = insert_edges(ge, pad(s, B), pad(d, B), **kw)
    go, mo = insert_edges_ref(go, pad(s, B), pad(d, B))
    assert np.array_equal(np.asarray(mi), np.asarray(mo))
    assert tree_equal(ge, go)
    assert int(ge.next_free) == ge.n_buckets + 2   # two overflow slabs
    # delete through the chain tail, then reinsert (tombstones stay)
    ge, _ = delete_edges(ge, pad([0] * 10, 16), pad(list(range(1, 11)), 16),
                         **kw)
    go, _ = delete_edges_ref(go, pad([0] * 10, 16),
                             pad(list(range(1, 11)), 16))
    assert tree_equal(ge, go)
    ge, _ = insert_edges(ge, pad([0] * 4, 8), pad([1, 2, 3, 999], 8), **kw)
    go, _ = insert_edges_ref(go, pad([0] * 4, 8), pad([1, 2, 3, 999], 8))
    assert tree_equal(ge, go)


# ---------------------------------------------------------------------------
# the engine's packed probe vs the oracle's whole-batch loop
# ---------------------------------------------------------------------------

HUB_SLABS = 40


def _hub_pool():
    """Vertex 0 owns a 40-slab chain (a hub); vertices 1..599 one slab
    each (no hashing: one bucket per vertex)."""
    n = HUB_SLABS * SLAB_WIDTH
    src = np.concatenate([np.zeros(n), np.arange(1, 600)])
    dst = np.concatenate([np.arange(1000, 1000 + n), np.arange(2, 601)])
    return from_edges_host(8192, src.astype(np.uint32),
                           dst.astype(np.uint32), hashing=False)


def _probe_batch(case, rng):
    """(src, dst) of one probe batch, padded with INVALID lanes."""
    if case == "invalid":
        return pad([], 512), pad([], 512)
    B = {"hub": 2048, "full": 512, "floor": 128, "trip1": 1024}[case]
    src = rng.integers(1, 600, B)
    dst = np.where(rng.random(B) < 0.5, src + 1, rng.integers(0, 8192, B))
    if case in ("hub", "floor"):
        # every 8th lane on the hub: present keys deep in its chain and
        # absent ones that walk all of it
        hub = np.arange(B) % 8 == 0
        src[hub] = 0
        dst[hub] = rng.integers(1000, 1000 + 2 * HUB_SLABS * SLAB_WIDTH,
                                hub.sum())
    if case == "full":
        # half the lanes hit the hub past its first slab: after trip 1 the
        # live lanes fill the next stage to its last slot
        src[:B // 2] = 0
        dst[:B // 2] = rng.integers(1000 + SLAB_WIDTH,
                                    1000 + HUB_SLABS * SLAB_WIDTH, B // 2)
    return pad(src[:B - 5], B), pad(dst[:B - 5], B)


@pytest.mark.parametrize("case", ["hub", "full", "floor", "trip1",
                                  "invalid"])
def test_packed_probe_matches_whole_batch_oracle(case):
    from repro.kernels.slab_update import probe_counted
    from repro.kernels.slab_update.ops import PACK_FLOOR, probe_packed
    from repro.kernels.slab_update.ref import batch_valid, edge_buckets
    g = _hub_pool()
    src, dst = _probe_batch(case, np.random.default_rng(7))
    valid = batch_valid(g, src, dst)
    b = edge_buckets(g, src, dst, valid)
    *want, wc = jax.jit(probe_counted)(g, b, dst, valid)
    *got, pc = jax.jit(probe_packed)(g, b, dst, valid)
    for w, x in zip(want, got):                      # found, slab, lane
        assert np.array_equal(np.asarray(w), np.asarray(x))
    assert (int(pc.hops), int(pc.visits), pc.width) == \
        (int(wc.hops), int(wc.visits), wc.width)
    assert int(wc.rows) == int(wc.hops) * wc.width and int(wc.packs) == 0
    hops, rows, packs = int(pc.hops), int(pc.rows), int(pc.packs)
    if case in ("hub", "full"):
        assert bool(np.asarray(got[0]).any())
        assert packs >= 1 and rows < hops * pc.width
    else:
        assert packs == 0 and rows == hops * pc.width
    if case == "hub":
        assert hops == HUB_SLABS
    if case == "floor":
        assert pc.width <= PACK_FLOOR
    if case == "trip1":
        assert hops == 1
    if case == "invalid":
        assert hops == int(pc.visits) == 0


# ---------------------------------------------------------------------------
# engine vs host set-oracle property test (satellite: mixed epochs,
# overflow chains, tombstones, deleted-then-reinserted pairs)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(
    st.tuples(st.sampled_from(["ins", "del", "reins"]),
              st.lists(st.tuples(st.integers(0, 11), st.integers(0, 11)),
                       min_size=1, max_size=10)),
    min_size=1, max_size=8))
def test_engine_property_matches_set_oracle(ops):
    g = empty(12, np.full(12, 2, np.int32), 384)
    oracle = set()
    deleted_once = set()
    B = 16
    epoch = 0
    for kind, pairs in ops:
        if kind == "reins" and deleted_once:
            # explicitly exercise deleted-then-reinserted pairs
            pairs = list(deleted_once)[:B]
        src = pad([p[0] for p in pairs], B)
        dst = pad([p[1] for p in pairs], B)
        if kind == "del":
            g, mask = delete_edges(g, src, dst, impl="jnp")
            deleted_once |= (oracle & set(pairs))
            oracle -= set(pairs)
        else:
            g, mask = insert_edges(g, src, dst, impl="jnp")
            oracle |= set(pairs)
        epoch += 1
        if epoch % 2 == 0:
            g = update_slab_pointers(g)      # close epochs mid-stream
    assert edges_in_graph(g) == oracle
    assert int(g.n_edges) == len(oracle)
    deg = np.zeros(12, np.int64)
    for s, _ in oracle:
        deg[s] += 1
    assert np.array_equal(np.asarray(g.degree, dtype=np.int64), deg)
    # membership queries agree with the set oracle for every pair ever seen
    universe = sorted(oracle | deleted_once)
    if universe:
        qs = pad([p[0] for p in universe], next_pow2(len(universe), 16))
        qd = pad([p[1] for p in universe], next_pow2(len(universe), 16))
        got = np.asarray(query_edges(g, qs, qd))[:len(universe)]
        want = [p in oracle for p in universe]
        assert got.tolist() == want


# ---------------------------------------------------------------------------
# query validity (satellite fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ENGINE_IMPLS + ["oracle"])
def test_query_sentinel_dst_returns_false(impl):
    """EMPTY/TOMBSTONE/INVALID dst must return False, not match sentinel
    lanes in partially filled slabs (EMPTY_KEY used to false-positive)."""
    kw = impl_kw(impl) if impl != "oracle" else dict(impl="oracle")
    g = empty(16, np.ones(16, np.int32), 64)
    g, _ = insert_edges(g, pad([3], 4), pad([5], 4))
    queries = jnp.asarray([EMPTY_KEY, TOMBSTONE_KEY, INVALID_VERTEX,
                           np.uint32(5)], jnp.uint32)
    found = query_edges(g, pad([3, 3, 3, 3], 4), queries, **kw)
    assert np.asarray(found).tolist() == [False, False, False, True]
    # out-of-range / sentinel src also stays False (uint32 compare — ids in
    # [2^31, 2^32) must not wrap negative and pass an int32 bound check)
    found = query_edges(g, jnp.asarray([0x80000000, INVALID_VERTEX, 16, 3],
                                       jnp.uint32), pad([5, 5, 5, 5], 4), **kw)
    assert np.asarray(found).tolist() == [False, False, False, True]


def test_delete_sentinel_dst_is_noop():
    """Deleting a sentinel dst must not tombstone an EMPTY lane."""
    g = empty(16, np.ones(16, np.int32), 64)
    g, _ = insert_edges(g, pad([3], 4), pad([5], 4))
    g2, mask = delete_edges(g, pad([3], 4),
                            jnp.asarray([EMPTY_KEY] * 4, jnp.uint32))
    assert not np.asarray(mask).any()
    assert tree_equal(g2, g)
    assert (np.asarray(g2.keys) == np.uint32(TOMBSTONE_KEY)).sum() == 0


# ---------------------------------------------------------------------------
# fused mixed batch + stacked multi-view dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ENGINE_IMPLS)
def test_apply_update_fused_matches_sequential(impl):
    rng = np.random.default_rng(3)
    V = 32
    kw = impl_kw(impl)
    g = empty(V, np.full(V, 2, np.int32), 512)
    go = empty(V, np.full(V, 2, np.int32), 512)
    for step in range(3):
        s = rng.integers(0, V, 12).astype(np.uint32)
        d = rng.integers(0, V, 12).astype(np.uint32)
        ds = rng.integers(0, V, 8).astype(np.uint32)
        dd = rng.integers(0, V, 8).astype(np.uint32)
        g, im, dm = apply_update(g, pad(s, 16), pad(d, 16), None,
                                 pad(ds, 8), pad(dd, 8), **kw)
        go, dmo = delete_edges_ref(go, pad(ds, 8), pad(dd, 8))
        go, imo = insert_edges_ref(go, pad(s, 16), pad(d, 16))
        assert np.array_equal(np.asarray(im), np.asarray(imo))
        assert np.array_equal(np.asarray(dm), np.asarray(dmo))
        assert tree_equal(g, go)


def test_update_views_matches_per_view_sequential():
    """The stacked dispatch must equal the legacy one-view-at-a-time path:
    forward/transpose mirror, symmetric keeps the union semantics."""
    rng = np.random.default_rng(5)
    V = 20
    src = rng.integers(0, V, 60).astype(np.uint32)
    dst = rng.integers(0, V, 60).astype(np.uint32)

    def build():
        fwd = from_edges_host(V, src, dst, hashing=False, slack_slabs=256)
        tr = from_edges_host(V, dst, src, hashing=False, slack_slabs=256)
        sym = from_edges_host(V, np.concatenate([src, dst]),
                              np.concatenate([dst, src]), hashing=False,
                              slack_slabs=256)
        return fwd, tr, sym

    ins_s, ins_d = pad(rng.integers(0, V, 10), 16), pad(
        rng.integers(0, V, 10), 16)
    del_s, del_d = pad(src[:6], 8), pad(dst[:6], 8)

    views, im, dm = update_views(build(),
                                 ("forward", "transpose", "symmetric"),
                                 ins=(ins_s, ins_d, None),
                                 dels=(del_s, del_d))

    # legacy sequence (PR-2 store semantics) through the oracle
    fwd, tr, sym = build()
    fwd, dmo = delete_edges_ref(fwd, del_s, del_d)
    tr, _ = delete_edges_ref(tr, del_d, del_s)
    rev = query_edges_ref(fwd, del_d, del_s)
    gone = ~rev
    s2 = jnp.concatenate([jnp.where(gone, del_s, INVALID_VERTEX),
                          jnp.where(gone, del_d, INVALID_VERTEX)])
    d2 = jnp.concatenate([del_d, del_s])
    sym, _ = delete_edges_ref(sym, s2, d2)
    fwd, imo = insert_edges_ref(fwd, ins_s, ins_d)
    tr, _ = insert_edges_ref(tr, ins_d, ins_s)
    sym, _ = insert_edges_ref(sym, jnp.concatenate([ins_s, ins_d]),
                              jnp.concatenate([ins_d, ins_s]))

    assert np.array_equal(np.asarray(im), np.asarray(imo))
    assert np.array_equal(np.asarray(dm), np.asarray(dmo))
    assert tree_equal(views[0], fwd)
    assert tree_equal(views[1], tr)
    assert tree_equal(views[2], sym)


def test_update_views_forward_only():
    g = from_edges_host(8, np.asarray([0, 1], np.uint32),
                        np.asarray([1, 2], np.uint32), hashing=False,
                        slack_slabs=64)
    (g2,), im, dm = update_views((g,), ("forward",),
                                 ins=(pad([2], 4), pad([3], 4), None))
    assert dm is None and bool(np.asarray(im)[0])
    assert edges_in_graph(g2) == {(0, 1), (1, 2), (2, 3)}


# ---------------------------------------------------------------------------
# capacity quantization + host-build vectorisation
# ---------------------------------------------------------------------------

def test_ensure_capacity_quantizes_to_pow2():
    g = empty(16, np.ones(16, np.int32), 70)
    g2 = ensure_capacity(g, 100)
    assert g2.capacity_slabs == next_pow2(g2.capacity_slabs)
    assert g2.capacity_slabs - int(g2.next_free) >= 100
    # repeated growth walks the pow2 ladder — identical shape for identical
    # demand, strictly larger pow2 for larger demand
    g3 = ensure_capacity(g2, 100)
    assert g3.capacity_slabs == g2.capacity_slabs   # no-op: already enough
    g4 = ensure_capacity(g2, 10 * g2.capacity_slabs)
    assert g4.capacity_slabs == next_pow2(g4.capacity_slabs)
    assert g4.capacity_slabs > g2.capacity_slabs


def test_from_edges_host_multi_overflow_chains():
    """The vectorised overflow chaining must reproduce insert semantics for
    buckets needing several chained overflow slabs."""
    V = 600
    n0 = 3 * SLAB_WIDTH + 11     # vertex 0: head + 3 overflow slabs
    n1 = SLAB_WIDTH + 2          # vertex 1: head + 1 overflow slab
    src = np.asarray([0] * n0 + [1] * n1 + [2], np.uint32)
    dst = np.asarray(list(range(1, n0 + 1)) + list(range(2, n1 + 2)) + [7],
                     np.uint32)
    gh = from_edges_host(V, src, dst, hashing=False)
    gi = empty(V, np.ones(V, np.int32), int(gh.capacity_slabs))
    gi, _ = insert_edges(gi, pad(src, 1024), pad(dst, 1024))
    assert edges_in_graph(gh) == edges_in_graph(gi)
    assert int(gh.n_edges) == int(gi.n_edges)
    assert np.array_equal(np.asarray(gh.degree), np.asarray(gi.degree))
    assert np.array_equal(np.asarray(gh.next_slab), np.asarray(gi.next_slab))
    assert np.array_equal(np.asarray(gh.slab_vertex),
                          np.asarray(gi.slab_vertex))
    assert np.array_equal(np.asarray(gh.tail_slab), np.asarray(gi.tail_slab))
    assert np.array_equal(np.asarray(gh.tail_fill), np.asarray(gi.tail_fill))


# ---------------------------------------------------------------------------
# GraphStore: exactly one host-side dedup per apply, all views
# ---------------------------------------------------------------------------

def test_store_apply_single_host_dedup(monkeypatch):
    from repro import stream
    from repro.stream import store as store_mod

    rng = np.random.default_rng(11)
    src = rng.integers(0, 30, 100).astype(np.uint32)
    dst = rng.integers(0, 30, 100).astype(np.uint32)
    store = stream.GraphStore.from_edges(30, src, dst)

    calls = {"canonical": 0, "dedup": 0}
    orig_canon = store_mod.canonical_batch
    orig_dedup = store_mod.dedup_pairs

    def counting_canon(*a, **k):
        calls["canonical"] += 1
        return orig_canon(*a, **k)

    def counting_dedup(*a, **k):
        calls["dedup"] += 1
        return orig_dedup(*a, **k)

    monkeypatch.setattr(store_mod, "canonical_batch", counting_canon)
    monkeypatch.setattr(store_mod, "dedup_pairs", counting_dedup)

    for k in range(3):
        calls["canonical"] = calls["dedup"] = 0
        store.apply(ins_src=[1, 2, 1], ins_dst=[5 + k, 6 + k, 5 + k],
                    del_src=src[k:k + 4], del_dst=dst[k:k + 4])
        # one canonicalisation per batch, for all three views; dedup_pairs
        # only runs inside it (insert half + delete half), never per view
        assert calls["canonical"] == 1
        assert calls["dedup"] <= 2

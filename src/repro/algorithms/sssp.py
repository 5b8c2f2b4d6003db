"""Dynamic single-source shortest paths (paper §4.2, Algs. 6, 10–12).

Tree-based SSSP: maintains the ⟨distance, parent⟩ dependence tree rooted at
SRC.  The GPU original packs the pair into one 64-bit word updated with
``atomicMin``; the TPU form keeps two planes and performs the identical
lexicographic-min relaxation with two ``segment_min`` passes (deterministic —
ties break toward the smaller parent id, same invariant as the paper).

Incremental: the inserted batch seeds the edge frontier; iterate the static
kernel to convergence (Alg. 6 lines 12–14 + epilogue).

Decremental: invalidate destinations of deleted tree edges (Alg. 11),
propagate invalidation down the dependence tree (Alg. 12 — here via pointer
doubling, O(log depth) sweeps instead of the paper's per-vertex ancestor walk:
a TPU-friendly beyond-paper change with identical semantics), re-seed the
frontier from every surviving→invalidated edge, then run the same epilogue.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.slab_graph import SlabGraph
from ..core.worklist import expand_vertices, pool_edges
from ..kernels.slab_sweep.ops import slice_rows, sweep_vertices

INF = jnp.float32(1e30)
NO_PARENT = jnp.int32(-1)


class TreeState(NamedTuple):
    dist: jnp.ndarray    # (V,) float32
    parent: jnp.ndarray  # (V,) int32


def init_state(n_vertices: int, src: int) -> TreeState:
    """Alg. 6 line 3: all INF/INVALID except the source (dist 0, parent=SRC)."""
    dist = jnp.full((n_vertices,), INF, jnp.float32).at[src].set(0.0)
    parent = jnp.full((n_vertices,), NO_PARENT, jnp.int32).at[src].set(src)
    return TreeState(dist, parent)


def _apply_relax(state: TreeState, dmin: jnp.ndarray, pmin: jnp.ndarray
                 ) -> Tuple[TreeState, jnp.ndarray]:
    """Fold the ⟨dmin, pmin⟩ candidate planes into the dependence tree —
    the shared epilogue of both relaxation data paths."""
    improved = (dmin < state.dist) | \
               ((dmin == state.dist) & (pmin < state.parent) & (dmin < INF))
    dist = jnp.where(improved, dmin, state.dist)
    parent = jnp.where(improved, pmin, state.parent)
    return TreeState(dist, parent), improved


def relax_edges(state: TreeState, esrc: jnp.ndarray, edst: jnp.ndarray,
                ew: jnp.ndarray, emask: jnp.ndarray
                ) -> Tuple[TreeState, jnp.ndarray]:
    """One batched relaxation (the SSSP_Kernel atomicMin, Alg. 10 line 9).

    Returns (new state, per-vertex improved mask).  Lexicographic
    ⟨distance, parent⟩ min via two segment_min passes.  This is the
    edge-list reference path (and the one batch prologues use — a batch IS
    an edge list); the per-iteration hot loop runs ``relax_sweep``.
    """
    n = state.dist.shape[0]
    s = jnp.where(emask, esrc.astype(jnp.int32), 0)
    d = jnp.where(emask, edst.astype(jnp.int32), n)
    cand = jnp.where(emask, state.dist[s] + ew, INF)
    dmin = jax.ops.segment_min(cand, d, num_segments=n + 1)[:n]
    at_min = emask & (cand <= dmin[jnp.minimum(d, n - 1)]) & (d < n)
    pcand = jnp.where(at_min, s, jnp.int32(2 ** 31 - 1))
    pmin = jax.ops.segment_min(pcand, d, num_segments=n + 1)[:n]
    return _apply_relax(state, dmin, pmin)


def relax_sweep(g_in: SlabGraph, state: TreeState, frontier: jnp.ndarray
                ) -> Tuple[TreeState, jnp.ndarray]:
    """One relaxation through the fused slab-sweep engine.

    ``g_in`` is the in-edge (transposed) graph: slab owner = destination,
    lane keys = source, weight pool = w(src→dst).  Two frontier-masked
    sweeps — min-plus for the distance plane, arg-min-plus for the
    deterministic parent tie-break — replace expand_vertices' EdgeFrontier
    materialization + double scatter.  Bit-identical to ``relax_edges``
    over the frontier's out-edges (min is exact; the per-edge f32 adds are
    the same adds).
    """
    dmin = sweep_vertices(g_in, state.dist, semiring="min_plus",
                          frontier=frontier)
    pmin = sweep_vertices(g_in, state.dist, semiring="arg_min_plus",
                          frontier=frontier, target=dmin)
    return _apply_relax(state, dmin, pmin)


def _compact_vertices(improved: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Vertex frontier from an improved mask (warpenqueuefrontier analogue)."""
    n = improved.shape[0]
    m = improved.astype(jnp.int32)
    pos = jnp.cumsum(m) - m
    verts = jnp.zeros((n,), jnp.uint32).at[
        jnp.where(improved, pos, n)].set(
        jnp.arange(n, dtype=jnp.uint32), mode="drop")
    cnt = jnp.sum(m)
    vmask = jnp.arange(n) < cnt
    return verts, vmask, cnt


@partial(jax.jit, static_argnames=("edge_capacity", "max_bpv", "max_iters",
                                   "rows"))
def run_to_convergence(g: SlabGraph, state: TreeState, improved0: jnp.ndarray,
                       *, edge_capacity: int, max_bpv: int = 1,
                       max_iters: int = 100000,
                       g_in: Optional[SlabGraph] = None,
                       rows: Optional[int] = None
                       ) -> Tuple[TreeState, jnp.ndarray]:
    """Common epilogue (Alg. 6 lines 22–27): relax the improved frontier,
    repeat until it empties.  Returns (state, iterations).

    With ``g_in`` (the transposed graph, ``core.transpose_host(g)``) the hot
    loop is one fused slab sweep per plane — the improved mask IS the
    frontier bitmask, no vertex compaction, no EdgeFrontier.  Without it,
    the expand_vertices reference path runs (also the fallback when only
    the out-edge view exists, e.g. mid-update-stream).  ``rows`` (static)
    bounds the sweeps to ``g_in``'s allocated slab prefix
    (``GraphStore.sweep_rows``; bit-identical to the full pool).
    """
    if g_in is not None:
        g_in = slice_rows(g_in, rows)

    def cond(carry):
        _, improved, it = carry
        return jnp.any(improved) & (it < max_iters)

    def body_sweep(carry):
        state, improved, it = carry
        state, improved = relax_sweep(g_in, state, improved)
        return state, improved, it + 1

    def body_expand(carry):
        state, improved, it = carry
        verts, vmask, _ = _compact_vertices(improved)
        ef = expand_vertices(g, verts, vmask, out_capacity=edge_capacity,
                             max_bpv=max_bpv)
        emask = jnp.arange(edge_capacity) < ef.size
        w = ef.weight if g.weighted else jnp.ones((edge_capacity,), jnp.float32)
        state, improved = relax_edges(state, ef.src, ef.dst, w, emask)
        return state, improved, it + 1

    body = body_expand if g_in is None else body_sweep
    state, _, iters = jax.lax.while_loop(
        cond, body, (state, improved0, jnp.asarray(0, jnp.int32)))
    return state, iters


# ---------------------------------------------------------------------------
# static
# ---------------------------------------------------------------------------

def sssp_static(g: SlabGraph, src: int, *, edge_capacity: int,
                max_bpv: int = 1,
                g_in: Optional[SlabGraph] = None,
                rows: Optional[int] = None
                ) -> Tuple[TreeState, jnp.ndarray]:
    """Alg. 6 lines 1–9: seed with the source's out-edges, iterate."""
    state = init_state(g.n_vertices, src)
    improved0 = jnp.zeros((g.n_vertices,), bool).at[src].set(True)
    return run_to_convergence(g, state, improved0,
                              edge_capacity=edge_capacity, max_bpv=max_bpv,
                              g_in=g_in, rows=rows)


# ---------------------------------------------------------------------------
# incremental
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("edge_capacity", "max_bpv", "rows"))
def sssp_incremental(g: SlabGraph, state: TreeState, bsrc: jnp.ndarray,
                     bdst: jnp.ndarray, bw: jnp.ndarray, bmask: jnp.ndarray,
                     *, edge_capacity: int, max_bpv: int = 1,
                     g_in: Optional[SlabGraph] = None,
                     rows: Optional[int] = None
                     ) -> Tuple[TreeState, jnp.ndarray]:
    """Incremental prologue (Alg. 6 lines 12–14): the inserted batch IS the
    initial edge frontier (genuinely an edge list — it stays on
    ``relax_edges``); then the common epilogue, swept when ``g_in`` (the
    post-update transpose) is supplied."""
    state, improved = relax_edges(state, bsrc, bdst, bw, bmask)
    return run_to_convergence(g, state, improved,
                              edge_capacity=edge_capacity, max_bpv=max_bpv,
                              g_in=g_in, rows=rows)


# ---------------------------------------------------------------------------
# decremental
# ---------------------------------------------------------------------------

def _invalidate(state: TreeState, bsrc, bdst, bmask) -> TreeState:
    """Alg. 11: a deleted edge (u,v) that is a tree edge invalidates v."""
    n = state.dist.shape[0]
    v = jnp.where(bmask, bdst.astype(jnp.int32), n)
    is_tree = bmask & (state.parent[jnp.minimum(v, n - 1)] ==
                       bsrc.astype(jnp.int32))
    tgt = jnp.where(is_tree, v, n)
    dist = state.dist.at[tgt].set(INF, mode="drop")
    parent = state.parent.at[tgt].set(NO_PARENT, mode="drop")
    return TreeState(dist, parent)


def _propagate_invalidation(state: TreeState, src: int,
                            n_rounds: int) -> TreeState:
    """Alg. 12 via pointer doubling: v survives iff its parent chain reaches
    SRC through un-invalidated vertices.  O(log depth) gathers."""
    n = state.dist.shape[0]
    reach = jnp.zeros((n,), bool).at[src].set(True)
    anc = jnp.where((state.dist < INF), state.parent, NO_PARENT)
    anc = anc.at[src].set(NO_PARENT)

    def body(_, carry):
        reach, anc = carry
        has = anc >= 0
        a = jnp.maximum(anc, 0)
        reach = reach | (has & reach[a])
        anc = jnp.where(has, anc[a], NO_PARENT)
        return reach, anc

    reach, _ = jax.lax.fori_loop(0, n_rounds, body, (reach, anc))
    dist = jnp.where(reach, state.dist, INF)
    parent = jnp.where(reach, state.parent, NO_PARENT)
    return TreeState(dist, parent)


@partial(jax.jit, static_argnames=("src", "edge_capacity", "max_bpv",
                                   "n_rounds", "rows"))
def sssp_decremental(g: SlabGraph, state: TreeState, bsrc: jnp.ndarray,
                     bdst: jnp.ndarray, bmask: jnp.ndarray, *, src: int,
                     edge_capacity: int, max_bpv: int = 1,
                     n_rounds: int = 32,
                     g_in: Optional[SlabGraph] = None,
                     rows: Optional[int] = None
                     ) -> Tuple[TreeState, jnp.ndarray]:
    """Decremental prologue (Alg. 6 lines 16–20) + common epilogue.

    ``g`` must already have the batch deleted.  The re-seeding frontier is
    every edge from a surviving vertex into an invalidated one, found with a
    masked full-pool relaxation (CreateDecrementalFrontier as a sweep — no
    compaction needed on TPU).
    """
    state = _invalidate(state, bsrc, bdst, bmask)
    state = _propagate_invalidation(state, src, n_rounds)
    alive = state.dist < INF

    if g_in is not None:
        g_in = slice_rows(g_in, rows)
        # the same re-seeding relaxation as one frontier-masked sweep
        # (frontier = surviving sources, result kept at invalidated
        # targets): no pool-sized edge list, which at Graph500 scale 21
        # would not fit next to the views in a 16 GB v5e
        dmin = sweep_vertices(g_in, state.dist, semiring="min_plus",
                              frontier=alive)
        pmin = sweep_vertices(g_in, state.dist, semiring="arg_min_plus",
                              frontier=alive, target=dmin)
        state, improved = _apply_relax(
            state, jnp.where(alive, INF, dmin),
            jnp.where(alive, jnp.int32(2 ** 31 - 1), pmin))
        return run_to_convergence(g, state, improved,
                                  edge_capacity=edge_capacity,
                                  max_bpv=max_bpv, g_in=g_in)

    view = pool_edges(g)
    fsrc = view.src.reshape(-1)
    fdst = view.dst.reshape(-1)
    fw = (view.weight.reshape(-1) if g.weighted
          else jnp.ones_like(fsrc, jnp.float32))
    fvalid = view.valid.reshape(-1)
    d_clip = jnp.where(fvalid, fdst.astype(jnp.int32), 0)
    s_clip = jnp.where(fvalid, fsrc, 0)
    emask = fvalid & alive[s_clip] & ~alive[d_clip]
    state, improved = relax_edges(state, fsrc.astype(jnp.uint32),
                                  fdst.astype(jnp.uint32), fw, emask)
    return run_to_convergence(g, state, improved,
                              edge_capacity=edge_capacity, max_bpv=max_bpv,
                              g_in=g_in)


# ---------------------------------------------------------------------------
# repro.stream registration hook
# ---------------------------------------------------------------------------

def stream_property(src: int, *, edge_capacity: int, max_bpv: int = 1,
                    n_rounds: int = 32):
    """PropertySpec: the ⟨distance, parent⟩ SSSP dependence tree from ``src``.
    Deleted batch edges run the decremental invalidate/reseed path, inserted
    edges the incremental relax prologue; both converge by sweeping the
    store's transpose view.  Unweighted stores fall back to unit weights."""
    from ..stream.properties import PropertySpec

    def _init(store):
        state, _ = sssp_static(store.forward, src,
                               edge_capacity=edge_capacity, max_bpv=max_bpv,
                               g_in=store.transpose, rows=store.sweep_rows())
        return state

    def _on_batch(store, state, batch):
        if batch.del_src is not None:
            state, _ = sssp_decremental(store.forward, state, batch.del_src,
                                        batch.del_dst, batch.del_mask,
                                        src=src, edge_capacity=edge_capacity,
                                        max_bpv=max_bpv, n_rounds=n_rounds,
                                        g_in=store.transpose,
                                        rows=store.sweep_rows())
        if batch.ins_src is not None:
            w = (batch.ins_w if batch.ins_w is not None
                 else jnp.ones_like(batch.ins_src, jnp.float32))
            state, _ = sssp_incremental(store.forward, state, batch.ins_src,
                                        batch.ins_dst, w, batch.ins_mask,
                                        edge_capacity=edge_capacity,
                                        max_bpv=max_bpv, g_in=store.transpose,
                                        rows=store.sweep_rows())
        return state

    # a deleting epoch's catch-up runs two convergence loops (decremental,
    # then incremental) where a refresh runs one: replay one epoch at most
    return PropertySpec(
        name=f"sssp_{src}", init=_init, on_batch=_on_batch, refresh=_init,
        max_replay=1,
        state_like=lambda n: TreeState(jnp.zeros((n,), jnp.float32),
                                       jnp.zeros((n,), jnp.int32)))

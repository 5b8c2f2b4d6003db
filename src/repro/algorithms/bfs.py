"""Dynamic BFS (paper §4.2, §6.1).

Two variants, matching the paper's evaluation:
  * VANILLA — level-synchronous static BFS, 32-bit distances only (the fast
    static path; no dependence tree).
  * TREE    — ⟨distance,parent⟩ dependence tree via the SSSP engine with unit
    weights: this is the variant that supports incremental / decremental
    updates (paper: "the incremental/decremental BFS algorithm uses the same
    kernels as that of incremental/decremental SSSP").
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.slab_graph import SlabGraph
from ..core.worklist import expand_vertices
from ..kernels.slab_sweep.ops import sweep_vertices
from .sssp import (INF, TreeState, init_state, run_to_convergence,
                   relax_edges, sssp_decremental, sssp_incremental,
                   _compact_vertices)

UNREACHED = jnp.int32(2 ** 30)


@partial(jax.jit, static_argnames=("src", "edge_capacity", "max_bpv",
                                   "max_iters"))
def bfs_vanilla(g: SlabGraph, *, src: int, edge_capacity: int,
                max_bpv: int = 1, max_iters: int = 100000,
                g_in: Optional[SlabGraph] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Level-based static BFS; returns (levels int32, iterations).

    With ``g_in`` (transposed graph, ``core.transpose_host(g)``) each level
    is ONE fused sweep: per vertex, count in-neighbors inside the current
    frontier (sum semiring over the frontier indicator) — no vertex
    compaction, no EdgeFrontier, no ``edge_capacity`` pressure.  Without it,
    the expand_vertices reference path runs.
    """
    n = g.n_vertices
    dist = jnp.full((n,), UNREACHED, jnp.int32).at[src].set(0)
    newly = jnp.zeros((n,), bool).at[src].set(True)

    def cond(carry):
        _, newly, it = carry
        return jnp.any(newly) & (it < max_iters)

    def body_sweep(carry):
        dist, newly, it = carry
        hits = sweep_vertices(g_in, newly.astype(jnp.int32), semiring="sum")
        newly = (hits > 0) & (dist == UNREACHED)
        dist = jnp.where(newly, it + 1, dist)
        return dist, newly, it + 1

    def body_expand(carry):
        dist, newly, it = carry
        verts, vmask, _ = _compact_vertices(newly)
        ef = expand_vertices(g, verts, vmask, out_capacity=edge_capacity,
                             max_bpv=max_bpv)
        emask = jnp.arange(edge_capacity) < ef.size
        d = jnp.where(emask, ef.dst.astype(jnp.int32), n)
        touched = jnp.zeros((n + 1,), bool).at[d].set(True, mode="drop")[:n]
        newly = touched & (dist == UNREACHED)
        dist = jnp.where(newly, it + 1, dist)
        return dist, newly, it + 1

    body = body_expand if g_in is None else body_sweep
    dist, _, iters = jax.lax.while_loop(
        cond, body, (dist, newly, jnp.asarray(0, jnp.int32)))
    return dist, iters


def bfs_tree_static(g: SlabGraph, src: int, *, edge_capacity: int,
                    max_bpv: int = 1,
                    g_in: Optional[SlabGraph] = None,
                    rows: Optional[int] = None
                    ) -> Tuple[TreeState, jnp.ndarray]:
    """TREE-BASED static BFS: SSSP engine, unit weights (64-bit pair updates
    on GPU; two-plane lexicographic segment-min here)."""
    state = init_state(g.n_vertices, src)
    improved0 = jnp.zeros((g.n_vertices,), bool).at[src].set(True)
    return run_to_convergence(g, state, improved0,
                              edge_capacity=edge_capacity, max_bpv=max_bpv,
                              g_in=g_in, rows=rows)


def bfs_incremental(g: SlabGraph, state: TreeState, bsrc, bdst, bmask, *,
                    edge_capacity: int, max_bpv: int = 1, g_in=None,
                    rows: Optional[int] = None):
    """Unit-weight incremental update via the SSSP engine."""
    bw = jnp.ones_like(bsrc, jnp.float32)
    return sssp_incremental(g, state, bsrc, bdst, bw, bmask,
                            edge_capacity=edge_capacity, max_bpv=max_bpv,
                            g_in=g_in, rows=rows)


def bfs_decremental(g: SlabGraph, state: TreeState, bsrc, bdst, bmask, *,
                    src: int, edge_capacity: int, max_bpv: int = 1,
                    g_in=None, rows: Optional[int] = None):
    return sssp_decremental(g, state, bsrc, bdst, bmask, src=src,
                            edge_capacity=edge_capacity, max_bpv=max_bpv,
                            g_in=g_in, rows=rows)


# ---------------------------------------------------------------------------
# repro.stream registration hook
# ---------------------------------------------------------------------------

def stream_property(src: int, *, edge_capacity: int, max_bpv: int = 1):
    """PropertySpec: ⟨distance, parent⟩ BFS tree from ``src``, maintained
    with the incremental/decremental SSSP engine (unit weights).  Deletions
    are handled first (the store applies them first), then insertions; the
    convergence loop sweeps the store's transpose view.

    Requires an UNWEIGHTED store: on a weighted one the batch prologue's unit
    weights would disagree with the sweep's stored weights (use
    ``sssp.stream_property`` there instead)."""
    from ..stream.properties import PropertySpec

    def _init(store):
        assert not store.weighted, \
            "bfs stream_property needs an unweighted GraphStore; " \
            "register sssp.stream_property on weighted stores"
        state, _ = bfs_tree_static(store.forward, src,
                                   edge_capacity=edge_capacity,
                                   max_bpv=max_bpv, g_in=store.transpose,
                                   rows=store.sweep_rows())
        return state

    def _on_batch(store, state, batch):
        if batch.del_src is not None:
            state, _ = bfs_decremental(store.forward, state, batch.del_src,
                                       batch.del_dst, batch.del_mask, src=src,
                                       edge_capacity=edge_capacity,
                                       max_bpv=max_bpv, g_in=store.transpose,
                                       rows=store.sweep_rows())
        if batch.ins_src is not None:
            state, _ = bfs_incremental(store.forward, state, batch.ins_src,
                                       batch.ins_dst, batch.ins_mask,
                                       edge_capacity=edge_capacity,
                                       max_bpv=max_bpv, g_in=store.transpose,
                                       rows=store.sweep_rows())
        return state

    # a deleting epoch's catch-up runs two convergence loops (decremental,
    # then incremental) where a refresh runs one: replay one epoch at most
    return PropertySpec(
        name=f"bfs_{src}", init=_init, on_batch=_on_batch, refresh=_init,
        max_replay=1,
        state_like=lambda n: TreeState(jnp.zeros((n,), jnp.float32),
                                       jnp.zeros((n,), jnp.int32)))

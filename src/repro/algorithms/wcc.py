"""Weakly connected components (paper §4.4, §6.4) — static + incremental.

Static WCC: one sweep over every adjacency (UNION-ASYNC + full path
compression).  Incremental WCC is evaluated in the paper under four schemes,
all reproduced here:

  * ``naive``           — re-union over ALL slabs (ignorant of update locations)
  * ``slab_iterator``   — only vertices whose per-vertex update flag is set,
                          but all their adjacencies
  * ``update_iterator`` — only the lanes inserted this epoch (Fig. 12b/Table 6)
  * ``batch``           — union directly over the insert batch (the algorithmic
                          floor; equivalent labels, used by the serving driver)

Decremental WCC on GPUs is an open problem (paper §6.4) — same here; only
incremental is provided.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..core.slab_graph import SlabGraph
from ..core.union_find import (compress, init_parents, union_batch,
                               union_batch_counted)
from ..core.worklist import pool_edges, updated_lane_mask, updated_vertices
from ..kernels.slab_sweep.ops import sweep_vertices


def _compact_lanes(g: SlabGraph, lane_mask: jnp.ndarray, cap: int):
    """Prefix-sum compaction of masked pool lanes into dense (cap,) edge
    buffers — THE step that makes the iterator schemes pay off on TPU: the
    union's data movement becomes ∝ #selected lanes, not ∝ pool size
    (the lane-vector rendering of 'visit only those slabs')."""
    src = pool_edges(g).src.reshape(-1)
    dst = g.keys.reshape(-1)
    m = lane_mask.reshape(-1)
    mi = m.astype(jnp.int32)
    pos = jnp.cumsum(mi) - mi
    idx = jnp.where(m & (pos < cap), pos, cap)
    u = jnp.zeros((cap,), jnp.int32).at[idx].set(src, mode="drop")
    v = jnp.zeros((cap,), jnp.int32).at[idx].set(
        dst.astype(jnp.int32), mode="drop")
    n = jnp.minimum(jnp.sum(mi), cap)
    return u, v, jnp.arange(cap) < n


@partial(jax.jit, static_argnames=("cap",))
def _union_pool(parent: jnp.ndarray, g: SlabGraph,
                lane_mask: jnp.ndarray, *, cap: int):
    """The union over the masked pool lanes: (parents, hooking rounds)."""
    u, v, m = _compact_lanes(g, lane_mask, cap)
    return union_batch_counted(parent, u, v, m)


def _edge_cap(g: SlabGraph) -> int:
    from ..core.hashing import SLAB_WIDTH
    return g.capacity_slabs * SLAB_WIDTH


def wcc_static_counted(g: SlabGraph, *, cap: int | None = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``wcc_static`` and the union's hooking rounds (an int32 scalar)."""
    parent = init_parents(g.n_vertices)
    parent, rounds = _union_pool(parent, g, pool_edges(g).valid,
                                 cap=cap or _edge_cap(g))
    return compress(parent), rounds


def wcc_static(g: SlabGraph, *, cap: int | None = None) -> jnp.ndarray:
    """Single traversal over all adjacencies; returns per-vertex labels.
    With tracing on, the union's hooking rounds ride the caller's span as
    ``wcc.hook_rounds``."""
    labels, rounds = wcc_static_counted(g, cap=cap)
    obs.count("wcc.hook_rounds", rounds)
    return labels


def wcc_incremental_naive(parent: jnp.ndarray, g: SlabGraph, *,
                          cap: int | None = None) -> jnp.ndarray:
    """Naive scheme: traverse every slab list (running time ∝ |E|)."""
    return compress(_union_pool(parent, g, pool_edges(g).valid,
                                cap=cap or _edge_cap(g))[0])


@partial(jax.jit, static_argnames=("cap", "max_bpv"))
def wcc_incremental_slab_iterator(parent: jnp.ndarray, g: SlabGraph, *,
                                  cap: int, max_bpv: int = 4) -> jnp.ndarray:
    """SlabIterator scheme: ALL adjacencies of vertices with updates —
    compacts the flagged-vertex set then walks only their chains
    (cap bounds the touched-vertex adjacency mass)."""
    from ..core.worklist import expand_vertices
    uv = updated_vertices(g)                       # (V,) bool
    m = uv.astype(jnp.int32)
    pos = jnp.cumsum(m) - m
    verts = jnp.zeros((g.n_vertices,), jnp.uint32).at[
        jnp.where(uv, pos, g.n_vertices)].set(
        jnp.arange(g.n_vertices, dtype=jnp.uint32), mode="drop")
    vmask = jnp.arange(g.n_vertices) < jnp.sum(m)
    ef = expand_vertices(g, verts, vmask, out_capacity=cap, max_bpv=max_bpv)
    emask = jnp.arange(cap) < ef.size
    return compress(union_batch(parent,
                                jnp.where(emask, ef.src, 0).astype(jnp.int32),
                                jnp.where(emask, ef.dst, 0).astype(jnp.int32),
                                emask))


@partial(jax.jit, static_argnames=("cap", "max_buckets"))
def wcc_incremental_update_iterator(parent: jnp.ndarray, g: SlabGraph, *,
                                    cap: int,
                                    max_buckets: int = 0) -> jnp.ndarray:
    """UpdateIterator scheme: only slabs holding this epoch's inserts —
    O(#updated slabs) via the flagged-bucket chain walk (the paper's best
    scheme; cap ≈ 2× batch size)."""
    from ..core.worklist import updated_edges
    mb = max_buckets or cap
    ef = updated_edges(g, max_buckets=mb, out_capacity=cap)
    emask = jnp.arange(cap) < ef.size
    return compress(union_batch(parent,
                                jnp.where(emask, ef.src, 0).astype(jnp.int32),
                                jnp.where(emask, ef.dst, 0).astype(jnp.int32),
                                emask))


@jax.jit
def wcc_incremental_batch(parent: jnp.ndarray, bsrc: jnp.ndarray,
                          bdst: jnp.ndarray, bmask: jnp.ndarray) -> jnp.ndarray:
    """Union directly over the inserted batch."""
    u = jnp.where(bmask, bsrc, 0).astype(jnp.int32)
    v = jnp.where(bmask, bdst, 0).astype(jnp.int32)
    return compress(union_batch(parent, u, v, bmask))


# ---------------------------------------------------------------------------
# Min-label propagation on the slab-sweep engine
# ---------------------------------------------------------------------------
# The paper's WCC is union-find (above — kept as the incremental engine and
# the partition oracle).  Label propagation is the traversal-bound
# formulation that exercises the pool sweep: per super-step every vertex
# takes the min label over its neighborhood, frontier-masked to the labels
# that changed last round.  Converges to min-vertex-id per component.
# ``g`` must hold the SYMMETRIC adjacency (undirected view):
# ``core.transpose_host(g, symmetric=True)``.

@partial(jax.jit, static_argnames=("max_iters",))
def wcc_labelprop_sweep(g: SlabGraph, *, max_iters: int = 100000
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Frontier-masked min-semiring sweeps to a fixpoint.

    Returns (labels int32 — min vertex id per component, iterations).
    """
    n = g.n_vertices
    labels0 = jnp.arange(n, dtype=jnp.int32)
    changed0 = jnp.ones((n,), bool)

    def cond(carry):
        _, changed, it = carry
        return jnp.any(changed) & (it < max_iters)

    def body(carry):
        labels, changed, it = carry
        nbr_min = sweep_vertices(g, labels, semiring="min", frontier=changed)
        new = jnp.minimum(labels, nbr_min)
        return new, new < labels, it + 1

    labels, _, iters = jax.lax.while_loop(
        cond, body, (labels0, changed0, jnp.asarray(0, jnp.int32)))
    return labels, iters


@partial(jax.jit, static_argnames=("max_iters",))
def wcc_labelprop_ref(g: SlabGraph, *, max_iters: int = 100000
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-jnp oracle for ``wcc_labelprop_sweep``: the same frontier-masked
    min propagation as a flat lane-wise ``segment_min`` (no per-slab
    partials) — integer mins are exact, so results are bit-identical."""
    n = g.n_vertices
    view = pool_edges(g)
    owner = view.src.reshape(-1)
    valid = view.valid.reshape(-1)
    idx = jnp.where(valid, view.dst.reshape(-1), 0).astype(jnp.int32)
    labels0 = jnp.arange(n, dtype=jnp.int32)
    changed0 = jnp.ones((n,), bool)

    def cond(carry):
        _, changed, it = carry
        return jnp.any(changed) & (it < max_iters)

    def body(carry):
        labels, changed, it = carry
        m = valid & changed[idx]
        seg = jnp.where(m, owner, n)
        nbr_min = jax.ops.segment_min(
            jnp.where(m, labels[idx], jnp.int32(2 ** 31 - 1)), seg,
            num_segments=n + 1)[:n]
        new = jnp.minimum(labels, nbr_min)
        return new, new < labels, it + 1

    labels, _, iters = jax.lax.while_loop(
        cond, body, (labels0, changed0, jnp.asarray(0, jnp.int32)))
    return labels, iters


def count_components(labels: jnp.ndarray) -> int:
    return int(jnp.sum((labels == jnp.arange(labels.shape[0])).astype(jnp.int32)))


# ---------------------------------------------------------------------------
# repro.stream registration hook
# ---------------------------------------------------------------------------

def stream_property(*, cap: int | None = None):
    """PropertySpec: per-vertex component labels (min-id roots).  Insert-only
    epochs advance with ``wcc_incremental_batch``; epochs that actually delete
    edges fall back to the static recompute — decremental WCC on GPUs is an
    open problem (paper §6.4), and the same holds here."""
    from ..stream.properties import PropertySpec

    def _refresh(store):
        with obs.span("wcc.refresh") as sp:
            labels = wcc_static(store.forward, cap=cap)
            sp.sync_on(labels)
        return labels

    def _on_batch(store, labels, batch):
        if batch.n_deleted > 0:
            return _refresh(store)
        if batch.ins_src is not None:
            with obs.span("wcc.incremental") as sp:
                labels = wcc_incremental_batch(labels, batch.ins_src,
                                               batch.ins_dst, batch.ins_mask)
                sp.sync_on(labels)
        return labels

    # a deleting epoch's catch-up IS a refresh: replay one epoch at most
    return PropertySpec(
        name="wcc", init=_refresh, on_batch=_on_batch, refresh=_refresh,
        max_replay=1, state_like=lambda n: jnp.zeros((n,), jnp.int32))

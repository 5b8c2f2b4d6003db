"""Dispatch layer of the slab_intersect family (triangle counting, Alg. 9).

Mirrors ``slab_update.ops``: one traced body per operation, jit'd entry
points with ``impl="auto" | "pallas" | "jnp" | "oracle"`` selection,
``@timed_dispatch("slab_intersect")`` obs instrumentation on the public
wrappers, ``*_local`` aliases for use inside ``shard_map``, and a vmapped
shard-stacked form.

Engines for ``count_edges`` (Σ_edges |N_G1(u) ∩ N_G2(v)|):

* ``oracle`` — ``ref.count_edges_ref``, the original interpreted path kept
  verbatim (whole-batch while_loop, Python-unrolled lane chunks).
* ``jnp``    — scan-fused engine: same work-item layout, but the lane-chunk
  probe runs as a ``lax.scan`` over chunk slices inside the chain walk so
  the traced program stays O(1) in SLAB_WIDTH/lane_chunk instead of
  unrolling, and each chunk's probe is a single fused bucket chain-walk.
* ``pallas`` — ``kernel.slab_count_pallas``: tiled work items with per-tile
  termination at both the G2 walk and the G1 probe.  Interpret mode only:
  the TPU v5e compiler refuses its ``keys_ref[idx]`` gather (``Cannot do
  int indexing on TPU``).

``impl="auto"`` is ``jnp`` on every backend (``repro.kernels.resolve_impl``).

All three are bit-identical on the count (the sum is order-independent);
tests/test_triangle_stream.py holds them to the oracle per impl.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.batch import edge_buckets, probe
from ...core.hashing import INVALID_SLAB, SLAB_WIDTH, is_valid_vertex
from ...core.slab_graph import SlabGraph
from ...obs import timed_dispatch
from .. import resolve_impl
from .kernel import probe_hits_pallas, slab_count_pallas
from .ref import count_edges_ref, probe_hits_ref, search_edges_ref

IMPLS = ("auto", "pallas", "jnp", "oracle")

_STATIC = ("impl", "interpret", "max_bpv", "lane_chunk", "edges_per_tile")


def _resolve(impl: str, interpret: Optional[bool]):
    return resolve_impl(impl, interpret, xla="jnp",
                        impls=("pallas", "jnp", "oracle"))


def _work_items(g2: SlabGraph, us, vs, emask, *, max_bpv: int):
    """Flatten (edge, bucket) pairs: per-item G2 start cursor + u."""
    E = us.shape[0]
    v = jnp.where(emask, vs, 0).astype(jnp.int32)
    j = jnp.arange(max_bpv, dtype=jnp.int32)[None, :]
    bmask = emask[:, None] & (j < g2.bucket_count[v][:, None])
    cur0 = jnp.where(bmask, g2.bucket_offset[v][:, None] + j,
                     INVALID_SLAB).reshape(-1).astype(jnp.int32)
    u_flat = jnp.where(bmask, us[:, None].astype(jnp.int32),
                       0).reshape(-1)
    return cur0, u_flat, bmask.reshape(-1)


def _count_jnp(g1: SlabGraph, g2: SlabGraph, cur0, u_flat, m_flat, *,
               lane_chunk: int) -> jnp.ndarray:
    """Scan-fused jnp engine: chain walk with a lane-chunk scan inside."""
    n_chunks = SLAB_WIDTH // lane_chunk
    uu = jnp.broadcast_to(u_flat[:, None],
                          (u_flat.shape[0], lane_chunk)).reshape(-1)

    def cond(state):
        cur, _ = state
        return jnp.any(cur != INVALID_SLAB)

    def body(state):
        cur, total = state
        active = cur != INVALID_SLAB
        rows = g2.keys[jnp.maximum(cur, 0)]                    # (B, 128)
        wvalid = active[:, None] & is_valid_vertex(rows) & m_flat[:, None]
        # (B, n_chunks, K) -> scan over the chunk axis
        rc = rows.reshape(-1, n_chunks, lane_chunk).swapaxes(0, 1)
        mc = wvalid.reshape(-1, n_chunks, lane_chunk).swapaxes(0, 1)

        def chunk_step(tot, slc):
            w, m = slc
            found = search_edges_ref(g1, uu, w.reshape(-1), m.reshape(-1))
            return tot + jnp.sum(found.astype(jnp.int32)), None

        total, _ = jax.lax.scan(chunk_step, total, (rc, mc))
        cur = jnp.where(active, g2.next_slab[jnp.maximum(cur, 0)],
                        INVALID_SLAB)
        return cur, total

    _, total = jax.lax.while_loop(
        cond, body, (cur0, jnp.asarray(0, jnp.int32)))
    return total


def _count_body(g1: SlabGraph, g2: SlabGraph, us, vs, emask, *,
                impl: str, interpret: bool, max_bpv: int,
                lane_chunk: int, edges_per_tile: int) -> jnp.ndarray:
    if impl == "oracle":
        return count_edges_ref(g1, g2, us, vs, emask, max_bpv=max_bpv,
                               lane_chunk=lane_chunk)
    cur0, u_flat, m_flat = _work_items(g2, us, vs, emask, max_bpv=max_bpv)
    if impl == "jnp":
        return _count_jnp(g1, g2, cur0, u_flat, m_flat,
                          lane_chunk=lane_chunk)
    per_item = slab_count_pallas(
        g1.keys, g1.next_slab, g1.bucket_offset, g1.bucket_count,
        g2.keys, g2.next_slab, cur0, u_flat,
        edges_per_tile=edges_per_tile, lane_chunk=lane_chunk,
        interpret=interpret)
    return jnp.sum(per_item)


_count_jit = jax.jit(_count_body, static_argnames=_STATIC)


@timed_dispatch("slab_intersect")
def count_edges(g1: SlabGraph, g2: SlabGraph, us, vs, emask, *,
                impl: str = "auto", interpret: Optional[bool] = None,
                max_bpv: int = 4, lane_chunk: int = 32,
                edges_per_tile: int = 8) -> jnp.ndarray:
    """Alg. 9's ``Count(G1, G2, edges)``: Σ_edges |N_G1(u) ∩ N_G2(v)|.

    Per edge (u, v) in (us, vs, emask), candidates w are drawn from v's
    adjacency in G2 (bucket enumeration bounded by ``max_bpv``) and probed
    for membership (u, w) ∈ G1 through G1's hash index — so ``max_bpv``
    must only dominate G2's bucket counts, never G1's.
    """
    impl, interpret = _resolve(impl, interpret)
    return _count_jit(g1, g2, us, vs, emask, impl=impl, interpret=interpret,
                      max_bpv=max_bpv, lane_chunk=lane_chunk,
                      edges_per_tile=edges_per_tile)


# Inside shard_map / vmap the obs wrapper steps aside anyway; the raw traced
# body avoids even the python-level indirection.
count_edges_local = _count_body


def count_shards(graphs1, graphs2, us, vs, emask, *, impl: str = "auto",
                 interpret: Optional[bool] = None, max_bpv: int = 4,
                 lane_chunk: int = 32, edges_per_tile: int = 8
                 ) -> jnp.ndarray:
    """Shard-stacked ``count_edges``: leading axis S on every arg, (S,) out.

    ``graphs1``/``graphs2`` are stacked SlabGraphs (one pool pytree with an
    S-leading axis, as built by ``ShardedSlabGraph``); ``us``/``vs``/``emask``
    are (S, B) per-shard work queues.  Shards whose lanes are all masked
    contribute 0.
    """
    impl, interpret = _resolve(impl, interpret)
    body = partial(_count_body, impl=impl, interpret=interpret,
                   max_bpv=max_bpv, lane_chunk=lane_chunk,
                   edges_per_tile=edges_per_tile)
    return jax.jit(jax.vmap(body))(graphs1, graphs2, us, vs, emask)


@partial(jax.jit, static_argnames=("max_bpv", "max_chain"))
def adjacency_rows(g: SlabGraph, vs: jnp.ndarray, mask: jnp.ndarray, *,
                   max_bpv: int = 4, max_chain: int = 8) -> jnp.ndarray:
    """Slab rows of v's full adjacency: every bucket's chain, -1 padded.

    Returns (Q, max_bpv * max_chain) int32 pool rows; gathering ``g.keys``
    at the (clamped) rows and masking ``rows >= 0`` yields each query's
    candidate neighbour lanes.  Chains longer than ``max_chain`` truncate —
    callers size it from ``pool_stats``'s max chain length.
    """
    v = jnp.where(mask, vs, 0).astype(jnp.int32)
    j = jnp.arange(max_bpv, dtype=jnp.int32)[None, :]
    bmask = mask[:, None] & (j < g.bucket_count[v][:, None])
    cur = jnp.where(bmask, g.bucket_offset[v][:, None] + j,
                    INVALID_SLAB).astype(jnp.int32)        # (Q, max_bpv)

    def step(cur, _):
        nxt = jnp.where(cur != INVALID_SLAB,
                        g.next_slab[jnp.maximum(cur, 0)], INVALID_SLAB)
        return nxt, cur

    _, rows = jax.lax.scan(step, cur, None, length=max_chain)
    # (C, Q, max_bpv) -> (Q, max_bpv * C)
    return jnp.moveaxis(rows, 0, 2).reshape(vs.shape[0], -1)


@partial(jax.jit, static_argnames=("max_chain",))
def materialize_chains(g: SlabGraph, us: jnp.ndarray, ws: jnp.ndarray,
                       mask: jnp.ndarray, *, max_chain: int) -> jnp.ndarray:
    """For each (u,w) query, the slab rows of u's bucket chain, -1 padded.
    Chains longer than ``max_chain`` are truncated (callers size it from the
    pool's max chain length)."""
    b = edge_buckets(g, us, ws, mask)
    cur = jnp.where(mask, b, INVALID_SLAB).astype(jnp.int32)

    def step(cur, _):
        nxt = jnp.where(cur != INVALID_SLAB,
                        g.next_slab[jnp.maximum(cur, 0)], INVALID_SLAB)
        return nxt, cur

    _, rows = jax.lax.scan(step, cur, None, length=max_chain)
    return jnp.swapaxes(rows, 0, 1)  # (Q, C)


def search_edges_kernel(g: SlabGraph, us: jnp.ndarray, ws: jnp.ndarray,
                        mask: jnp.ndarray, *, max_chain: int = 8,
                        impl: str = "auto") -> jnp.ndarray:
    """Drop-in for ``algorithms.triangle.search_edges`` over materialized
    chains (``impl``: ``"auto"`` = ``"ref"``, or ``"pallas"``)."""
    impl, interpret = resolve_impl(impl, None, xla="ref",
                                   impls=("pallas", "ref"))
    rows = materialize_chains(g, us, ws, mask, max_chain=max_chain)
    if impl == "ref":
        return probe_hits_ref(ws, rows, g.keys) & mask
    return probe_hits_pallas(ws, rows, g.keys, interpret=interpret) & mask


__all__ = ["IMPLS", "count_edges", "count_edges_local", "count_shards",
           "adjacency_rows", "materialize_chains", "search_edges_kernel",
           "probe_hits_pallas", "probe_hits_ref", "count_edges_ref",
           "search_edges_ref", "slab_count_pallas"]

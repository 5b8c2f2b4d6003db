"""Dispatch + engine layer for the slab-compaction plane.

The third fused kernel family (after ``slab_sweep`` and ``slab_update``):
memory *maintenance*.  The update plane is deliberately append-only —
deletes flip lanes to TOMBSTONE and ``next_free`` only advances — so a
sustained insert+delete churn stream (the paper's core dynamic-graph
workload) monotonically inflates the pool while every O(pool) sweep and
chain walk pays for dead freight.  GraphVine-style on-GPU structure
maintenance is what keeps long streams flat; this module is that plane:

* ``compact``            — re-pack every bucket's survivors into the dense
  cold layout (chain-walk order preserved), rebuild chains/tails/degrees,
  reset the allocator, optionally *shrink* the pool down the same pow2
  jit-shape ladder ``ensure_capacity`` grows along.  Returns the compacted
  graph plus a ``CompactionReport`` carrying the old→new slab permutation
  (stale-handle invalidation) and the capacity movement.
* ``reclaim_free_slabs`` — the lightweight tier: unlink wholly-dead
  overflow slabs from their chains and push them onto the graph's
  free-slab recycling list, where insert placement re-allocates them
  before bumping ``next_free`` (the paper's SlabAlloc reuse analogue).
  No lane moves, no shape change, no handle invalidation.
* ``compact_shards`` / ``reclaim_shards`` — the same ops vmapped over a
  shard-stacked pool (one uniform post-compaction capacity so the stack
  stays rectangular).

Implementation selection (``impl``) mirrors the update engine:

* ``"pallas"`` — tiled census + per-tile-terminating chain-rank kernels
  (``kernel.py``), interpret mode only (for validation): Mosaic does not
  lower the census's ``cumsum`` nor the chain rank's ``dynamic_slice``, so
  the TPU v5e compiler refuses both;
* ``"jnp"``    — the same scan-based plan lowered through XLA: per-lane
  destinations from live-prefix ranks, NO whole-pool lane sort;
* ``"oracle"`` — the sort-based whole-pool rebuild (``ref.py``), bit-exact
  reference;
* ``"auto"``   — ``"jnp"`` on every backend (``repro.kernels.resolve_impl``).

All three produce leaf-for-leaf identical graphs and permutations
(tests/test_maintenance.py).  Compaction must run on a CLOSED epoch (the
stores call it right after ``update_slab_pointers``); it resets the
UpdateIterator state itself.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.hashing import EMPTY_KEY, INVALID_SLAB, SLAB_WIDTH
from ...core.slab_graph import SlabGraph, next_pow2
from ...obs import timed_dispatch
from .. import resolve_impl
from .kernel import chain_rank_pallas, slab_live_pallas
from .ref import (assemble, chain_order, compact_ref, live_lane_mask,
                  perm_of, rebuild_links, recount_degrees, slab_of_rank)

IMPLS = ("auto", "pallas", "jnp", "oracle")


def _resolve(impl: str, interpret: Optional[bool]):
    return resolve_impl(impl, interpret, xla="jnp",
                        impls=("pallas", "jnp", "oracle"))


@dataclasses.dataclass(frozen=True)
class CompactionReport:
    """What one compaction did — consumed by the maintenance policy layer,
    surfaced through store stats and the churn benchmark."""
    perm: jnp.ndarray        # (S_old,) old→new slab id, INVALID_SLAB = dead
    live_lanes: int          # lanes surviving the re-pack (== n_edges)
    live_slabs: int          # allocated rows after (n_buckets + overflow)
    old_capacity: int
    new_capacity: int
    old_next_free: int
    new_next_free: int

    @property
    def freed_slabs(self) -> int:
        return self.old_next_free - self.new_next_free

    @property
    def shrunk(self) -> bool:
        return self.new_capacity < self.old_capacity


# ----------------------------------------------------------------------------
# plan: per-slab live census + chain ranks (the two pool-wide passes)
# ----------------------------------------------------------------------------

def _plan_body(keys, slab_vertex, next_slab, *, n_buckets, impl, interpret,
               rows_per_block, buckets_per_tile):
    if impl == "pallas":
        live_cnt, lane_rank = slab_live_pallas(
            keys, slab_vertex, rows_per_block=rows_per_block,
            interpret=interpret)
        base_rank, bucket_of, _, counts = chain_rank_pallas(
            next_slab, live_cnt, n_buckets=n_buckets,
            buckets_per_tile=buckets_per_tile, interpret=interpret)
    else:
        live = live_lane_mask(keys, slab_vertex)
        li = live.astype(jnp.int32)
        live_cnt = jnp.sum(li, axis=1)
        lane_rank = jnp.cumsum(li, axis=1) - li
        base_rank, bucket_of, _, counts = chain_order(
            next_slab, live_cnt, n_buckets)
    return live_cnt, lane_rank, base_rank, bucket_of, counts


_plan_jit = jax.jit(_plan_body,
                    static_argnames=("n_buckets", "impl", "interpret",
                                     "rows_per_block", "buckets_per_tile"))


# ----------------------------------------------------------------------------
# commit: scatter survivors into the fresh dense pool (scan-based — no sort)
# ----------------------------------------------------------------------------

def _commit_body(g, live_cnt, lane_rank, base_rank, bucket_of, counts, *,
                 capacity_slabs):
    W = SLAB_WIDTH
    nb = g.n_buckets
    live = live_lane_mask(g.keys, g.slab_vertex)
    extra_off, total_slabs, nxt, sv, tail_slab, tail_fill = rebuild_links(
        counts, n_buckets=nb, bucket_vertex=g.bucket_vertex,
        capacity=capacity_slabs)

    # per-lane destination straight from the prefix ranks — the engine's
    # whole win over the oracle: no (S·W)-triple materialisation, no sort.
    rank = base_rank[:, None] + lane_rank
    dst_slab = jnp.where(live,
                         slab_of_rank(rank, bucket_of[:, None], extra_off,
                                      nb),
                         capacity_slabs)
    dst_lane = jnp.where(live, rank % W, 0)

    new_keys = jnp.full((capacity_slabs, W), EMPTY_KEY, jnp.uint32) \
        .at[dst_slab, dst_lane].set(g.keys, mode="drop")
    new_weights = None
    if g.weighted:
        new_weights = jnp.zeros((capacity_slabs, W), jnp.float32) \
            .at[dst_slab, dst_lane].set(g.weights, mode="drop")

    g2 = assemble(g, capacity=capacity_slabs, counts=counts,
                  new_keys=new_keys, new_weights=new_weights, nxt=nxt, sv=sv,
                  tail_slab=tail_slab, tail_fill=tail_fill,
                  total_slabs=total_slabs,
                  degree=recount_degrees(g, live_cnt))
    perm = perm_of(base_rank, bucket_of, live_cnt, extra_off,
                   n_buckets=nb, capacity_old=g.capacity_slabs)
    return g2, perm


_commit_jit = jax.jit(_commit_body, static_argnames=("capacity_slabs",))
_oracle_jit = jax.jit(compact_ref, static_argnames=("capacity_slabs",))


def _pick_capacity(needed: int, current: int, n_buckets: int, *,
                   capacity_slabs: Optional[int], slack_slabs: int,
                   shrink: bool) -> int:
    """The pow2 capacity ladder, downward: compacted pools land on the same
    jit shapes ``ensure_capacity`` grows through, and only shrink when the
    survivors fit a strictly lower rung."""
    if capacity_slabs is not None:
        cap = max(int(capacity_slabs), needed, n_buckets + 1)
        return cap
    cap = next_pow2(max(needed + slack_slabs, n_buckets + 1))
    if not shrink:
        cap = max(cap, current)
    return cap


@timed_dispatch("slab_compact")
def compact(g: SlabGraph, *, impl: str = "auto",
            interpret: Optional[bool] = None,
            capacity_slabs: Optional[int] = None, slack_slabs: int = 64,
            shrink: bool = True, rows_per_block: int = 256,
            buckets_per_tile: int = 256
            ) -> Tuple[SlabGraph, CompactionReport]:
    """Compact one SlabGraph (host entry — sizes the target pool, then runs
    the shape-static rebuild).

    ``shrink=True`` lets the new capacity drop to the pow2 rung holding
    ``survivor slabs + slack_slabs``; ``shrink=False`` keeps the current
    capacity (pure de-fragmentation).  ``capacity_slabs`` pins the target
    exactly (clamped up to what the survivors need).  Must be called on a
    closed epoch; the result's epoch state is reset.
    """
    impl, interpret = _resolve(impl, interpret)
    plan_impl = "jnp" if impl == "oracle" else impl
    live_cnt, lane_rank, base_rank, bucket_of, counts = _plan_jit(
        g.keys, g.slab_vertex, g.next_slab, n_buckets=g.n_buckets,
        impl=plan_impl, interpret=interpret, rows_per_block=rows_per_block,
        buckets_per_tile=buckets_per_tile)
    counts_h = jax.device_get(counts)
    extra = -(-counts_h // SLAB_WIDTH) - 1
    needed = g.n_buckets + int(extra[extra > 0].sum())
    cap = _pick_capacity(needed, g.capacity_slabs, g.n_buckets,
                         capacity_slabs=capacity_slabs,
                         slack_slabs=slack_slabs, shrink=shrink)
    if impl == "oracle":
        g2, perm = _oracle_jit(g, capacity_slabs=cap)
    else:
        g2, perm = _commit_jit(g, live_cnt, lane_rank, base_rank, bucket_of,
                               counts, capacity_slabs=cap)
    report = CompactionReport(
        perm=perm,
        live_lanes=int(counts_h.sum()),
        live_slabs=needed,
        old_capacity=g.capacity_slabs,
        new_capacity=cap,
        old_next_free=int(g.next_free),
        new_next_free=int(g2.next_free))
    return g2, report


# ----------------------------------------------------------------------------
# lightweight tier: wholly-dead slab reclamation → free-slab recycling list
# ----------------------------------------------------------------------------

def _reclaim_body(g: SlabGraph):
    W = SLAB_WIDTH
    S = g.capacity_slabs
    nb = g.n_buckets
    live = live_lane_mask(g.keys, g.slab_vertex)
    live_cnt = jnp.sum(live.astype(jnp.int32), axis=1)
    rows = jnp.arange(S, dtype=jnp.int32)
    dead = (g.slab_vertex >= 0) & (rows >= nb) & (live_cnt == 0)

    # unlink dead runs: pointer-jump every next pointer over dead slabs
    def jcond(nxt):
        return jnp.any((nxt >= 0) & dead[jnp.maximum(nxt, 0)])

    def jbody(nxt):
        t = jnp.maximum(nxt, 0)
        jump = (nxt >= 0) & dead[t]
        return jnp.where(jump, nxt[t], nxt)

    nxt = jax.lax.while_loop(jcond, jbody, g.next_slab)
    new_next = jnp.where(dead, INVALID_SLAB, nxt)

    # tails moved wherever a chain's dead suffix was cut: re-walk the
    # pruned chains (head row = bucket id)
    heads = jnp.arange(nb, dtype=jnp.int32)

    def tcond(state):
        return jnp.any(state[0] != INVALID_SLAB)

    def tbody(state):
        cur, tail = state
        active = cur != INVALID_SLAB
        nxt_b = jnp.where(active, new_next[jnp.maximum(cur, 0)],
                          INVALID_SLAB)
        has = nxt_b != INVALID_SLAB
        return nxt_b, jnp.where(has, nxt_b, tail)

    _, tail2 = jax.lax.while_loop(tcond, tbody, (heads, heads))
    # an unchanged tail keeps its fill; a cut tail was full by construction
    # (it overflowed into the slabs that just died)
    fill2 = jnp.where(tail2 == g.tail_slab, g.tail_fill, W).astype(jnp.int32)

    # push freed ids (ascending) onto the recycling list; scrub their rows
    m = dead.astype(jnp.int32)
    pos = g.free_top + jnp.cumsum(m) - m
    free_list = g.free_list.at[jnp.where(dead, pos, S)].set(rows, mode="drop")
    n_freed = jnp.sum(m)

    keys = jnp.where(dead[:, None], EMPTY_KEY, g.keys)
    weights = g.weights
    if g.weighted:
        weights = jnp.where(dead[:, None], 0.0, g.weights)
    g2 = dataclasses.replace(
        g, keys=keys, weights=weights, next_slab=new_next,
        slab_vertex=jnp.where(dead, -1, g.slab_vertex),
        tail_slab=tail2, tail_fill=fill2,
        upd_flag=jnp.zeros_like(g.upd_flag), upd_slab=tail2, upd_lane=fill2,
        epoch_next_free=g.next_free,
        free_list=free_list, free_top=g.free_top + n_freed,
        slab_new=jnp.zeros_like(g.slab_new))
    return g2, n_freed


_reclaim_jit = jax.jit(_reclaim_body)


@timed_dispatch("slab_compact")
def reclaim_free_slabs(g: SlabGraph) -> Tuple[SlabGraph, int]:
    """Unlink wholly-dead overflow slabs and recycle them (see module doc).

    Head slabs are never reclaimed (they ARE the bucket entry points).
    Chain contents and traversal order are untouched — only dead hops
    disappear — so queries and sweeps are invariant.  Must run on a closed
    epoch; the result's epoch state is reset.  Returns
    ``(graph, n_reclaimed)``.
    """
    g2, n = _reclaim_jit(g)
    return g2, int(n)


# ----------------------------------------------------------------------------
# shard-stacked variants (vmapped over the leading shard dim)
# ----------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_buckets", "impl", "interpret",
                                   "rows_per_block", "buckets_per_tile"))
def _vplan_jit(keys, slab_vertex, next_slab, *, n_buckets, impl, interpret,
               rows_per_block, buckets_per_tile):
    f = partial(_plan_body, n_buckets=n_buckets, impl=impl,
                interpret=interpret, rows_per_block=rows_per_block,
                buckets_per_tile=buckets_per_tile)
    return jax.vmap(f)(keys, slab_vertex, next_slab)


@partial(jax.jit, static_argnames=("capacity_slabs",))
def _vcommit_jit(graphs, live_cnt, lane_rank, base_rank, bucket_of, counts,
                 *, capacity_slabs):
    f = partial(_commit_body, capacity_slabs=capacity_slabs)
    return jax.vmap(f)(graphs, live_cnt, lane_rank, base_rank, bucket_of,
                       counts)


@partial(jax.jit, static_argnames=("capacity_slabs",))
def _voracle_jit(graphs, *, capacity_slabs):
    return jax.vmap(partial(compact_ref,
                            capacity_slabs=capacity_slabs))(graphs)


_vreclaim_jit = jax.jit(jax.vmap(_reclaim_body))


@timed_dispatch("slab_compact")
def compact_shards(graphs: SlabGraph, *, impl: str = "auto",
                   interpret: Optional[bool] = None,
                   capacity_slabs: Optional[int] = None,
                   slack_slabs: int = 64, shrink: bool = True,
                   rows_per_block: int = 256, buckets_per_tile: int = 256
                   ) -> Tuple[SlabGraph, CompactionReport]:
    """Compact a SHARD-STACKED graph (leading shard dim on every data leaf).

    All shards land on ONE pow2 capacity — the max survivor need across
    shards plus slack — so the stacked pools stay rectangular.  The report
    aggregates over shards; ``perm`` is (n_shards, S_old).
    """
    impl, interpret = _resolve(impl, interpret)
    plan_impl = "jnp" if impl == "oracle" else impl
    g0 = jax.tree_util.tree_map(lambda x: x[0], graphs)
    nb = g0.n_buckets
    plan = _vplan_jit(graphs.keys, graphs.slab_vertex, graphs.next_slab,
                      n_buckets=nb, impl=plan_impl, interpret=interpret,
                      rows_per_block=rows_per_block,
                      buckets_per_tile=buckets_per_tile)
    live_cnt, lane_rank, base_rank, bucket_of, counts = plan
    counts_h = jax.device_get(counts)                      # (n_shards, nb)
    extra_h = np.maximum(-(-counts_h // SLAB_WIDTH) - 1, 0)
    needed = nb + int(extra_h.sum(axis=1).max())
    cap = _pick_capacity(needed, g0.capacity_slabs, nb,
                         capacity_slabs=capacity_slabs,
                         slack_slabs=slack_slabs, shrink=shrink)
    if impl == "oracle":
        g2, perm = _voracle_jit(graphs, capacity_slabs=cap)
    else:
        g2, perm = _vcommit_jit(graphs, live_cnt, lane_rank, base_rank,
                                bucket_of, counts, capacity_slabs=cap)
    report = CompactionReport(
        perm=perm,
        live_lanes=int(counts_h.sum()),
        live_slabs=needed,
        old_capacity=g0.capacity_slabs,
        new_capacity=cap,
        old_next_free=int(jnp.max(graphs.next_free)),
        new_next_free=int(jnp.max(g2.next_free)))
    return g2, report


@timed_dispatch("slab_compact")
def reclaim_shards(graphs: SlabGraph) -> Tuple[SlabGraph, int]:
    """``reclaim_free_slabs`` vmapped over the shard dim (capacity is
    unchanged, so no re-stacking is needed).  Returns total freed count."""
    g2, n = _vreclaim_jit(graphs)
    return g2, int(jnp.sum(n))


__all__ = ["IMPLS", "CompactionReport", "compact", "compact_shards",
           "reclaim_free_slabs", "reclaim_shards",
           "slab_live_pallas", "chain_rank_pallas"]

"""SlabGraph — Meerkat's pooled, hash-bucketed dynamic adjacency on TPU.

The GPU original keeps, per vertex, a SlabHash table whose buckets are linked
lists of 128-byte slabs, with *all* head slabs carved out of one pooled
allocation (the paper's memory-management contribution, Table 5).  The TPU/JAX
translation keeps the exact same object model but as a struct-of-arrays pytree:

  * one key pool        ``keys      : (capacity_slabs, 128) uint32``
  * one weight pool     ``weights   : (capacity_slabs, 128) float32`` (weighted)
  * chain "pointers"    ``next_slab : (capacity_slabs,) int32`` (-1 = end)
  * slab ownership      ``slab_vertex : (capacity_slabs,) int32`` — the
    materialised form of IterationScheme2's ⟨bucket_vertex⟩ vector
  * per-vertex bucket ranges via ``bucket_offset`` (exclusive scan of
    ``bucket_count`` — verbatim the paper's head-slab placement)
  * head slab of global bucket ``b`` is pool row ``b`` (head slabs occupy the
    pool prefix, one pooled allocation)
  * O(1) append state per bucket (``tail_slab`` / ``tail_fill``)
  * UpdateIterator state per bucket (``upd_flag`` / ``upd_slab`` / ``upd_lane``)
    plus ``epoch_next_free`` — every slab allocated after the last
    ``update_slab_pointers()`` is wholly "new"
  * a functional bump allocator (``next_free``) fronted by a free-slab
    recycling list (``free_list`` / ``free_top``) — the SlabAlloc reuse
    analogue: slabs reclaimed by the maintenance plane
    (``kernels/slab_compact``) are handed back to insert placement before
    the bump pointer advances
  * ``slab_new`` — per-slab "allocated this epoch" flag consumed by the
    UpdateIterator lane mask (recycled slabs sit below the old
    ``epoch_next_free`` watermark, so a bare row-id compare can no longer
    tell new slabs from old ones)

Everything is fixed-capacity inside jit; ``ensure_capacity`` (host side) grows
the pool between steps, mirroring the role of SlabAlloc's pre-allocated pool;
``kernels/slab_compact`` compacts and shrinks it back down under churn.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import (EMPTY_KEY, INVALID_SLAB, SLAB_WIDTH, TOMBSTONE_KEY)


@partial(jax.tree_util.register_dataclass,
         data_fields=["keys", "weights", "next_slab", "slab_vertex",
                      "bucket_offset", "bucket_count", "bucket_vertex",
                      "tail_slab", "tail_fill",
                      "upd_flag", "upd_slab", "upd_lane",
                      "next_free", "epoch_next_free",
                      "free_list", "free_top", "slab_new",
                      "degree", "n_edges"],
         meta_fields=["n_vertices", "n_buckets", "weighted"])
@dataclasses.dataclass(frozen=True)
class SlabGraph:
    # --- pools -------------------------------------------------------------
    keys: jnp.ndarray            # (S, 128) uint32, EMPTY/TOMBSTONE sentinels
    weights: Optional[jnp.ndarray]  # (S, 128) float32 or None
    next_slab: jnp.ndarray       # (S,) int32; -1 terminates a slab list
    slab_vertex: jnp.ndarray     # (S,) int32; owner vertex, -1 = unallocated
    # --- per-vertex bucket layout (paper: exclusive_scan(bucket_count)) -----
    bucket_offset: jnp.ndarray   # (V+1,) int32
    bucket_count: jnp.ndarray    # (V,) int32
    bucket_vertex: jnp.ndarray   # (B,) int32 — global bucket -> owner vertex
    # --- O(1) append state ---------------------------------------------------
    tail_slab: jnp.ndarray       # (B,) int32
    tail_fill: jnp.ndarray       # (B,) int32 in [0, 128]
    # --- UpdateIterator state (paper §3.4, Fig. 2) ---------------------------
    upd_flag: jnp.ndarray        # (B,) bool — bucket received inserts this epoch
    upd_slab: jnp.ndarray        # (B,) int32 — first slab holding new edges
    upd_lane: jnp.ndarray        # (B,) int32 — first new lane within upd_slab
    # --- allocator -----------------------------------------------------------
    next_free: jnp.ndarray       # () int32 — bump pointer into the pool
    epoch_next_free: jnp.ndarray # () int32 — next_free at last update_slab_pointers
    # --- free-slab recycling (SlabAlloc's reuse list, fed by maintenance) -----
    free_list: jnp.ndarray       # (S,) int32 — reclaimed slab ids in [0, free_top)
    free_top: jnp.ndarray        # () int32 — live length of free_list
    slab_new: jnp.ndarray        # (S,) bool — slab was (re)allocated this epoch
    # --- bookkeeping ----------------------------------------------------------
    degree: jnp.ndarray          # (V,) int32 — current stored-adjacency degree
    n_edges: jnp.ndarray         # () int32
    # --- static metadata -------------------------------------------------------
    n_vertices: int
    n_buckets: int
    weighted: bool

    # ------------------------------------------------------------------ props
    @property
    def capacity_slabs(self) -> int:
        return self.keys.shape[0]

    def nbytes(self) -> int:
        """Device bytes held by the representation (Table 5 accounting)."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(self):
            total += leaf.size * leaf.dtype.itemsize
        return int(total)


# ============================================================================
# Construction
# ============================================================================

def plan_buckets(n_vertices: int, init_degree: np.ndarray, *,
                 load_factor: float = 0.7, hashing: bool = True) -> np.ndarray:
    """Paper §3.1: #head-slabs per vertex from initial degree and load factor.

    With hashing disabled every vertex gets exactly one slab list (the
    "single bucket" mode that improves slab occupancy for traversal-bound
    algorithms — paper §6.1).
    """
    if not hashing:
        return np.ones(n_vertices, dtype=np.int32)
    per_slab = SLAB_WIDTH * load_factor
    return np.maximum(1, np.ceil(init_degree / per_slab)).astype(np.int32)


def empty(n_vertices: int, bucket_count: np.ndarray, capacity_slabs: int, *,
          weighted: bool = False) -> SlabGraph:
    """Allocate an empty graph: the single pooled allocation of head slabs.

    Head slab of global bucket ``b`` is pool row ``b``; overflow slabs are bump
    allocated from row ``n_buckets`` upward.
    """
    bucket_count = np.asarray(bucket_count, dtype=np.int32)
    assert bucket_count.shape == (n_vertices,)
    bucket_offset = np.zeros(n_vertices + 1, dtype=np.int32)
    np.cumsum(bucket_count, out=bucket_offset[1:])
    n_buckets = int(bucket_offset[-1])
    capacity_slabs = int(max(capacity_slabs, n_buckets + 1))
    bucket_vertex = np.repeat(np.arange(n_vertices, dtype=np.int32), bucket_count)

    slab_vertex = np.full(capacity_slabs, -1, dtype=np.int32)
    slab_vertex[:n_buckets] = bucket_vertex

    return SlabGraph(
        keys=jnp.full((capacity_slabs, SLAB_WIDTH), EMPTY_KEY, dtype=jnp.uint32),
        weights=(jnp.zeros((capacity_slabs, SLAB_WIDTH), dtype=jnp.float32)
                 if weighted else None),
        next_slab=jnp.full((capacity_slabs,), INVALID_SLAB, dtype=jnp.int32),
        slab_vertex=jnp.asarray(slab_vertex),
        bucket_offset=jnp.asarray(bucket_offset),
        bucket_count=jnp.asarray(bucket_count),
        bucket_vertex=jnp.asarray(bucket_vertex),
        tail_slab=jnp.arange(n_buckets, dtype=jnp.int32),
        tail_fill=jnp.zeros((n_buckets,), dtype=jnp.int32),
        upd_flag=jnp.zeros((n_buckets,), dtype=bool),
        upd_slab=jnp.arange(n_buckets, dtype=jnp.int32),
        upd_lane=jnp.zeros((n_buckets,), dtype=jnp.int32),
        next_free=jnp.asarray(n_buckets, dtype=jnp.int32),
        epoch_next_free=jnp.asarray(n_buckets, dtype=jnp.int32),
        free_list=jnp.full((capacity_slabs,), INVALID_SLAB, dtype=jnp.int32),
        free_top=jnp.asarray(0, dtype=jnp.int32),
        slab_new=jnp.zeros((capacity_slabs,), dtype=bool),
        degree=jnp.zeros((n_vertices,), dtype=jnp.int32),
        n_edges=jnp.asarray(0, dtype=jnp.int32),
        n_vertices=n_vertices,
        n_buckets=n_buckets,
        weighted=weighted,
    )


def next_pow2(n: int, lo: int = 64) -> int:
    """Smallest power of two ≥ max(n, lo)."""
    return 1 << max(int(n) - 1, lo - 1, 1).bit_length()


def ensure_capacity(g: SlabGraph, extra_slabs: int) -> SlabGraph:
    """Host-side pool growth (outside jit) — the SlabAlloc re-pool analogue.

    Guarantees at least ``extra_slabs`` allocatable slabs.  Recycled slabs
    on the free list count toward that budget (insert placement drains the
    free list before bumping ``next_free``), so a churn-maintained pool can
    absorb batches without growing at all.  Grown capacities are quantized
    to powers of two (and grow by ≥ 1.5× so the amortised cost matches GPU
    pool allocators): a stream of update batches walks a small ladder of
    pool shapes instead of retriggering jit specialization of every entry
    point on each growth step.
    """
    free = g.capacity_slabs - int(g.next_free) + int(g.free_top)
    if free >= extra_slabs:
        return g
    target = max(int(g.next_free) - int(g.free_top) + extra_slabs,
                 g.capacity_slabs + g.capacity_slabs // 2)
    grow = next_pow2(target) - g.capacity_slabs

    def pad_rows(a, fill, dtype):
        pad = jnp.full((grow,) + a.shape[1:], fill, dtype=dtype)
        return jnp.concatenate([a, pad], axis=0)

    return dataclasses.replace(
        g,
        keys=pad_rows(g.keys, EMPTY_KEY, jnp.uint32),
        weights=(pad_rows(g.weights, 0.0, jnp.float32) if g.weighted else None),
        next_slab=pad_rows(g.next_slab, INVALID_SLAB, jnp.int32),
        slab_vertex=pad_rows(g.slab_vertex, -1, jnp.int32),
        free_list=pad_rows(g.free_list, INVALID_SLAB, jnp.int32),
        slab_new=pad_rows(g.slab_new, False, bool),
    )


def update_slab_pointers(g: SlabGraph) -> SlabGraph:
    """Paper's ``Graph.UpdateSlabPointers()`` (Fig. 2).

    Closes the current update epoch: clears every bucket's ``is_updated`` flag
    and repositions (upd_slab, upd_lane) to where the *next* insertion will
    land — the current tail slab / fill (lane = 128 == INVALID_LANE case falls
    out naturally: the next insert opens a fresh slab).  ``epoch_next_free``
    records the allocator watermark so "slab is wholly new" is a single compare.
    """
    return dataclasses.replace(
        g,
        upd_flag=jnp.zeros_like(g.upd_flag),
        upd_slab=g.tail_slab,
        upd_lane=g.tail_fill,
        epoch_next_free=g.next_free,
        slab_new=jnp.zeros_like(g.slab_new),
    )


# ============================================================================
# Host-side bulk construction (numpy fast path for experiments)
# ============================================================================

def from_edges_host(n_vertices: int, src: np.ndarray, dst: np.ndarray,
                    weights: Optional[np.ndarray] = None, *,
                    load_factor: float = 0.7, hashing: bool = True,
                    slack_slabs: int = 0) -> SlabGraph:
    """Build a SlabGraph from a static edge list on the host.

    Semantically identical to inserting the edges through ``insert_edges`` on
    an empty graph (the benchmarks do exactly that to measure build
    throughput); this numpy path exists so large test graphs construct fast.
    Duplicate (src,dst) pairs are dropped, matching insert semantics.
    """
    return jax.tree.map(jnp.asarray, from_edges_numpy(
        n_vertices, src, dst, weights, load_factor=load_factor,
        hashing=hashing, slack_slabs=slack_slabs))


def from_edges_numpy(n_vertices: int, src: np.ndarray, dst: np.ndarray,
                     weights: Optional[np.ndarray] = None, *,
                     load_factor: float = 0.7, hashing: bool = True,
                     slack_slabs: int = 0) -> SlabGraph:
    """``from_edges_host`` with numpy leaves, still in host memory — for
    callers that place the pools on devices themselves."""
    src = np.asarray(src, dtype=np.uint32)
    dst = np.asarray(dst, dtype=np.uint32)
    w = None if weights is None else np.asarray(weights, dtype=np.float32)

    # dedup
    key = src.astype(np.uint64) * np.uint64(2 ** 32) + dst.astype(np.uint64)
    _, uniq_idx = np.unique(key, return_index=True)
    uniq_idx.sort()
    src, dst = src[uniq_idx], dst[uniq_idx]
    if w is not None:
        w = w[uniq_idx]

    deg = np.bincount(src.astype(np.int64), minlength=n_vertices).astype(np.int32)
    bucket_count = plan_buckets(n_vertices, deg, load_factor=load_factor,
                                hashing=hashing)
    bucket_offset = np.zeros(n_vertices + 1, dtype=np.int64)
    np.cumsum(bucket_count, out=bucket_offset[1:])
    n_buckets = int(bucket_offset[-1])

    # global bucket per edge (same multiplicative hash as device code)
    h = ((dst.astype(np.uint64) * 2654435761) & 0xFFFFFFFF).astype(np.uint64) >> 8
    b = bucket_offset[src.astype(np.int64)] + (h % bucket_count[src.astype(np.int64)])
    order = np.argsort(b, kind="stable")
    b_s, dst_s = b[order], dst[order]
    w_s = None if w is None else w[order]

    # per-bucket fill counts and slab layout
    per_bucket = np.bincount(b_s.astype(np.int64), minlength=n_buckets)
    extra = np.maximum(0, -(-per_bucket // SLAB_WIDTH) - 1)
    extra_off = np.zeros(n_buckets + 1, dtype=np.int64)
    np.cumsum(extra, out=extra_off[1:])
    total_slabs = n_buckets + int(extra_off[-1])
    # pow2-quantized like ensure_capacity: a cold-built store and a grown
    # store land on the SAME jit-shape ladder for the same size class.
    capacity = next_pow2(total_slabs + max(slack_slabs, total_slabs // 2 + 64))

    keys = np.full((capacity, SLAB_WIDTH), np.uint32(EMPTY_KEY), dtype=np.uint32)
    wpool = (np.zeros((capacity, SLAB_WIDTH), dtype=np.float32)
             if w is not None else None)
    nxt = np.full(capacity, -1, dtype=np.int32)
    slab_vertex = np.full(capacity, -1, dtype=np.int32)
    bucket_vertex = np.repeat(np.arange(n_vertices, dtype=np.int32), bucket_count)
    slab_vertex[:n_buckets] = bucket_vertex

    # rank of each edge within its bucket
    start = np.zeros(len(b_s), dtype=np.int64)
    if len(b_s):
        run_start = np.ones(len(b_s), dtype=bool)
        run_start[1:] = b_s[1:] != b_s[:-1]
        idx = np.arange(len(b_s), dtype=np.int64)
        start = np.maximum.accumulate(np.where(run_start, idx, 0))
    rank = np.arange(len(b_s), dtype=np.int64) - start

    slab_of = np.where(rank < SLAB_WIDTH,
                       b_s.astype(np.int64),
                       n_buckets + extra_off[b_s.astype(np.int64)]
                       + (rank // SLAB_WIDTH) - 1)
    lane_of = rank % SLAB_WIDTH
    keys[slab_of, lane_of] = dst_s
    if wpool is not None:
        wpool[slab_of, lane_of] = w_s

    # chain links + ownership for overflow slabs — fully vectorised (the
    # interpreted per-bucket loop here was O(#buckets) on every bulk build):
    # overflow slab k (global row n_buckets+k) belongs to the bucket whose
    # [extra_off[b], extra_off[b+1]) range contains k, links to row k+1
    # unless it is its bucket's last overflow slab, and the bucket's head
    # chain enters at its first overflow slab.
    total_extra = int(extra_off[-1])
    if total_extra:
        has = extra > 0
        nxt[np.nonzero(has)[0]] = (n_buckets + extra_off[:-1][has]).astype(
            np.int32)
        own = np.repeat(np.arange(n_buckets, dtype=np.int64), extra)
        ids = n_buckets + np.arange(total_extra, dtype=np.int64)
        slab_vertex[ids] = bucket_vertex[own]
        is_last = (ids - n_buckets + 1) == extra_off[own + 1]
        nxt[ids[~is_last]] = (ids[~is_last] + 1).astype(np.int32)

    tail_slab = np.where(extra > 0, n_buckets + extra_off[:-1] + extra - 1,
                         np.arange(n_buckets)).astype(np.int32)
    tail_fill = np.where(per_bucket > 0,
                         per_bucket - (-(-per_bucket // SLAB_WIDTH) - 1) * SLAB_WIDTH,
                         0).astype(np.int32)

    return SlabGraph(
        keys=keys,
        weights=wpool,
        next_slab=nxt,
        slab_vertex=slab_vertex,
        bucket_offset=bucket_offset.astype(np.int32),
        bucket_count=bucket_count,
        bucket_vertex=bucket_vertex,
        tail_slab=tail_slab,
        tail_fill=tail_fill,
        upd_flag=np.zeros(n_buckets, dtype=bool),
        upd_slab=tail_slab,
        upd_lane=tail_fill,
        next_free=np.asarray(total_slabs, dtype=np.int32),
        epoch_next_free=np.asarray(total_slabs, dtype=np.int32),
        free_list=np.full((capacity,), -1, dtype=np.int32),
        free_top=np.asarray(0, dtype=np.int32),
        slab_new=np.zeros((capacity,), dtype=bool),
        degree=np.bincount(src.astype(np.int64),
                           minlength=n_vertices).astype(np.int32),
        n_edges=np.asarray(len(src), dtype=np.int32),
        n_vertices=n_vertices,
        n_buckets=n_buckets,
        weighted=w is not None,
    )


# ============================================================================
# Pool health (host side) — the maintenance plane's trigger inputs
# ============================================================================

def pool_stats(g: SlabGraph) -> dict:
    """Host-side pool-health snapshot driving ``MaintenancePolicy`` triggers.

    Lane accounting distinguishes *live* lanes (real neighbor ids) from
    *tombstone* lanes (deleted, still occupying a lane until compaction);
    ``dead_slabs`` counts allocated non-head slabs with zero live lanes —
    exactly what ``reclaim_free_slabs`` can hand back to the free list.
    Chain lengths are slabs per bucket (head included), the multiplier every
    chain-walk probe pays.
    """
    keys = np.asarray(g.keys)
    sv = np.asarray(g.slab_vertex)
    nxt = np.asarray(g.next_slab)
    S = g.capacity_slabs
    alloc = sv >= 0
    live_lane = alloc[:, None] & (keys < np.uint32(TOMBSTONE_KEY))
    tomb_lane = alloc[:, None] & (keys == np.uint32(TOMBSTONE_KEY))
    live_per_slab = live_lane.sum(axis=1)
    live_lanes = int(live_per_slab.sum())
    tombstone_lanes = int(tomb_lane.sum())
    allocated_slabs = int(alloc.sum())
    is_head = np.arange(S) < g.n_buckets
    dead_slabs = int((alloc & ~is_head & (live_per_slab == 0)).sum())

    # chain lengths: vectorised walk from every bucket head (head row = b)
    lengths = np.zeros(g.n_buckets, dtype=np.int64)
    cur = np.arange(g.n_buckets, dtype=np.int64)
    active = np.ones(g.n_buckets, dtype=bool)
    while active.any():
        lengths[active] += 1
        nxt_v = nxt[cur[active]]
        cur[active] = np.maximum(nxt_v, 0)
        active[active] = nxt_v >= 0

    occupied = live_lanes + tombstone_lanes
    return {
        "capacity_slabs": S,
        "next_free": int(g.next_free),
        "free_top": int(g.free_top),
        "free_slabs": S - int(g.next_free) + int(g.free_top),
        "allocated_slabs": allocated_slabs,
        "dead_slabs": dead_slabs,
        "live_lanes": live_lanes,
        "tombstone_lanes": tombstone_lanes,
        "tombstone_ratio": tombstone_lanes / max(1, occupied),
        "occupancy": live_lanes / max(1, allocated_slabs * SLAB_WIDTH),
        "max_chain": int(lengths.max()) if len(lengths) else 0,
        "mean_chain": float(lengths.mean()) if len(lengths) else 0.0,
        "pool_bytes": int(g.keys.size * 4 +
                          (g.weights.size * 4 if g.weighted else 0)),
        "n_edges": int(g.n_edges),
    }

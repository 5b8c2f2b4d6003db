"""Whole-pool jnp oracle for the slab-update engine.

These are the original ``core/batch.py`` implementations, kept verbatim as
the bit-exact reference the fused engine (``ops.py`` / ``kernel.py``) is
validated against, and as the interpret-mode fallback when neither the
Pallas nor the run-local jnp engine path is wanted.

Semantics notes (shared by oracle and engine — the contracts the tests pin):

* A batch lane is valid iff ``src < n_vertices`` (as uint32, so the
  INVALID_VERTEX pad and any id ≥ 2³¹ are rejected, not wrapped through an
  int32 cast) **and** ``dst`` is below the sentinel range
  (``is_valid_vertex``).  The dst guard is deliberately *sentinel*-based
  rather than ``dst < n_vertices``: the sharded layer stores **global**
  destination ids in shard-local tables, so any non-sentinel uint32 is a
  legitimate key — but EMPTY/TOMBSTONE/INVALID dst would otherwise probe
  (and on insert/delete, corrupt) sentinel lanes.
* Deletion only flips found lanes to TOMBSTONE_KEY (paper §6); the update
  plane never reuses a tombstoned lane — a deleted-then-reinserted pair
  lands in a fresh tail lane.  Reclaiming dead lanes/slabs is the
  maintenance plane's job (``kernels/slab_compact``, DESIGN.md §8), which
  feeds whole reclaimed slabs back through ``free_list``; insert placement
  here drains that list before bumping ``next_free``.
* Placement is the deterministic sort + prefix-scan scheme of DESIGN.md §2:
  results are bit-reproducible for a given batch, and the engine reproduces
  the exact pool layout of this oracle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.hashing import (INVALID_SLAB, SLAB_WIDTH, TOMBSTONE_KEY,
                             bucket_hash, is_valid_vertex)
from ...core.slab_graph import SlabGraph


# ----------------------------------------------------------------------------
# shared helpers (used by both the oracle below and the engine in ops.py)
# ----------------------------------------------------------------------------

def batch_valid(g: SlabGraph, src: jnp.ndarray,
                dst: jnp.ndarray) -> jnp.ndarray:
    """Valid-lane mask: in-range src AND non-sentinel dst (see module doc)."""
    return (src.astype(jnp.uint32) < jnp.uint32(g.n_vertices)) \
        & is_valid_vertex(dst)


def edge_buckets(g: SlabGraph, src: jnp.ndarray, dst: jnp.ndarray,
                 valid: jnp.ndarray) -> jnp.ndarray:
    """Global bucket id for each (src,dst); 0 for padded lanes (masked later)."""
    s = jnp.where(valid, src, 0).astype(jnp.int32)
    nb = g.bucket_count[s]
    b = g.bucket_offset[s] + bucket_hash(dst, nb)
    return jnp.where(valid, b, 0).astype(jnp.int32)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("hops", "visits", "rows", "packs"),
                   meta_fields=("width",))
@dataclasses.dataclass(frozen=True)
class ProbeCount:
    """The work of one probe loop: ``hops`` trips of its ``while_loop``s,
    ``visits`` the lanes still walking a chain summed over the trips,
    ``width`` the (static) batch width, ``rows`` the slab rows actually
    gathered and ``packs`` the times the loop packed its live lanes into
    a narrower one.  ``visits / rows`` is the share of gathered rows that
    belonged to a live query; ``visits / (hops * width)`` is what it
    would be if every trip gathered the whole batch."""
    hops: jnp.ndarray
    visits: jnp.ndarray
    width: int
    rows: jnp.ndarray
    packs: jnp.ndarray


def probe_counted(g: SlabGraph, bucket: jnp.ndarray, dst: jnp.ndarray,
                  valid: jnp.ndarray):
    """Walk each query's slab list; return (found, slab, lane) per query
    and the loop's ``ProbeCount``.

    The inner body is the warp-cooperative slab probe: one gathered slab row
    (128 lanes) per query per hop, lane-wide equality, ``ballot``→``any``.
    Whole-batch termination — every lane waits on the longest chain, so
    every trip gathers the whole batch (``rows == hops * width``).  This is
    the oracle's loop; the XLA engine (``ops.probe_packed``) packs the
    lanes still walking into narrower loops, and the Pallas kernel
    (``kernel.slab_probe_pallas``) terminates per tile.
    """
    B = bucket.shape[0]
    cur = jnp.where(valid, bucket, INVALID_SLAB).astype(jnp.int32)
    found = jnp.zeros((B,), dtype=bool)
    slab = jnp.full((B,), INVALID_SLAB, dtype=jnp.int32)
    lane = jnp.full((B,), -1, dtype=jnp.int32)
    # per-lane trips, summed once after the loop: an elementwise add per
    # trip, no reduction across the batch inside the loop
    steps = jnp.zeros((B,), jnp.int32)

    def cond(state):
        cur, *_ = state
        return jnp.any(cur != INVALID_SLAB)

    def body(state):
        cur, found, slab, lane, hops, steps = state
        live = cur != INVALID_SLAB
        rows = g.keys[jnp.maximum(cur, 0)]                       # (B, 128)
        hit = (rows == dst[:, None].astype(jnp.uint32)) & live[:, None]
        hit_any = jnp.any(hit, axis=1)
        hit_lane = jnp.argmax(hit, axis=1).astype(jnp.int32)
        newly = hit_any & ~found
        slab = jnp.where(newly, cur, slab)
        lane = jnp.where(newly, hit_lane, lane)
        found = found | hit_any
        nxt = g.next_slab[jnp.maximum(cur, 0)]
        cur = jnp.where(~live | found, INVALID_SLAB, nxt)
        return (cur, found, slab, lane, hops + 1,
                steps + live.astype(jnp.int32))

    _, found, slab, lane, hops, steps = jax.lax.while_loop(
        cond, body, (cur, found, slab, lane, jnp.zeros((), jnp.int32), steps))
    visits = jnp.sum(steps)
    return found, slab, lane, ProbeCount(
        hops=hops, visits=visits, width=B, rows=hops * B,
        packs=jnp.zeros((), jnp.int32))


def probe(g: SlabGraph, bucket: jnp.ndarray, dst: jnp.ndarray,
          valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``probe_counted`` without the count: (found, slab, lane) per query."""
    found, slab, lane, _ = probe_counted(g, bucket, dst, valid)
    return found, slab, lane


def sort_by_bucket(b, dst, valid):
    """Stable sort by (bucket, dst) with padded lanes pushed to the end."""
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    b_key = jnp.where(valid, b, big)
    order = jnp.lexsort((dst.astype(jnp.int32), b_key))
    return order, b_key[order]


# ----------------------------------------------------------------------------
# query
# ----------------------------------------------------------------------------

def query_edges_ref(g: SlabGraph, src: jnp.ndarray,
                    dst: jnp.ndarray) -> jnp.ndarray:
    """Batched membership query (paper's query benchmark, Fig. 5)."""
    src = src.astype(jnp.uint32)
    dst = dst.astype(jnp.uint32)
    valid = batch_valid(g, src, dst)
    b = edge_buckets(g, src, dst, valid)
    found, _, _ = probe(g, b, dst, valid)
    return found & valid


# ----------------------------------------------------------------------------
# insert
# ----------------------------------------------------------------------------

def insert_edges_ref(g: SlabGraph, src: jnp.ndarray, dst: jnp.ndarray,
                     w: Optional[jnp.ndarray] = None
                     ) -> Tuple[SlabGraph, jnp.ndarray]:
    """Batched ``InsertEdgeBatch``.  Returns (new graph, inserted mask).

    Pool must have ≥ batch_size free slabs (see ``ensure_capacity``); the
    worst case is every survivor opening a fresh slab in a distinct bucket.
    Sets the UpdateIterator fields for buckets that receive their first
    insert of the epoch (paper §3.4).
    """
    src = src.astype(jnp.uint32)
    dst = dst.astype(jnp.uint32)
    B = src.shape[0]
    valid = batch_valid(g, src, dst)

    b = edge_buckets(g, src, dst, valid)
    order, b_s = sort_by_bucket(b, dst, valid)
    dst_s = dst[order]
    src_s = src[order]
    valid_s = valid[order]
    w_s = None if w is None else w[order]

    # in-batch duplicate collapse on the sorted runs
    same_prev = jnp.zeros((B,), dtype=bool)
    if B > 1:
        same_prev = same_prev.at[1:].set(
            (b_s[1:] == b_s[:-1]) & (dst_s[1:] == dst_s[:-1]))
    cand = valid_s & ~same_prev

    # already-present rejection (one chain walk for the whole batch)
    exists, _, _ = probe(g, jnp.where(cand, b_s, 0), dst_s, cand)
    new = cand & ~exists

    # --- per-bucket counts & ranks over survivors ---------------------------
    nb = g.n_buckets
    b_clip = jnp.where(new, b_s, nb)  # park rejects in a scratch segment
    counts = jax.ops.segment_sum(new.astype(jnp.int32), b_clip,
                                 num_segments=nb + 1)[:nb]
    excl = jnp.cumsum(new.astype(jnp.int32)) - new.astype(jnp.int32)
    run_start = jnp.ones((B,), dtype=bool)
    if B > 1:
        run_start = run_start.at[1:].set(b_s[1:] != b_s[:-1])
    base = jax.lax.cummax(jnp.where(run_start, excl, -1))
    rank = jnp.where(new, excl - base, 0)

    # --- slab placement ------------------------------------------------------
    tail = g.tail_slab
    fill = g.tail_fill
    room = SLAB_WIDTH - fill                                   # (nb,)
    overflow = jnp.maximum(counts - room, 0)
    new_slabs = (overflow + SLAB_WIDTH - 1) // SLAB_WIDTH      # per bucket
    cum = jnp.cumsum(new_slabs)
    ord_base = cum - new_slabs              # bucket's first new-slab ordinal
    total_new = cum[-1]

    # allocation: the o-th new slab of this call pops the free-slab recycling
    # list (top first) while any reclaimed slabs remain, then falls back to
    # the bump allocator — identical ordinal→id resolution to the engine.
    k = jnp.arange(B, dtype=jnp.int32)
    take = jnp.minimum(total_new, g.free_top)
    recycled = g.free_list[jnp.clip(g.free_top - 1 - k, 0,
                                    g.capacity_slabs - 1)]
    alloc_ids = jnp.where(k < take, recycled, g.next_free + k - take)

    def slab_at(ordinal):
        return alloc_ids[jnp.clip(ordinal, 0, B - 1)]

    e_b = jnp.where(new, b_s, 0).astype(jnp.int32)
    e_room = room[e_b]
    in_tail = rank < e_room
    e_slab = jnp.where(in_tail, tail[e_b],
                       slab_at(ord_base[e_b] + (rank - e_room) // SLAB_WIDTH))
    e_lane = jnp.where(in_tail, fill[e_b] + rank,
                       (rank - e_room) % SLAB_WIDTH)
    # park rejected writes out of bounds; mode="drop" discards them
    e_slab = jnp.where(new, e_slab, g.capacity_slabs)
    e_lane = jnp.where(new, e_lane, 0)

    keys = g.keys.at[e_slab, e_lane].set(dst_s, mode="drop")
    weights = g.weights
    if g.weighted:
        wv = (jnp.zeros((B,), jnp.float32) if w_s is None else
              w_s.astype(jnp.float32))
        weights = g.weights.at[e_slab, e_lane].set(wv, mode="drop")

    # --- chain the freshly allocated slabs -----------------------------------
    # Allocated ids interleave recycled and bump slabs, so every link
    # resolves its ordinal through ``alloc_ids``.
    has_new = new_slabs > 0
    next_slab = g.next_slab
    # link old tail -> first new slab (only where the tail was exhausted)
    link_from = jnp.where(has_new, tail, g.capacity_slabs)
    next_slab = next_slab.at[link_from].set(slab_at(ord_base), mode="drop")
    # link new slabs amongst themselves: ordinal o points to o+1's id unless
    # it is the bucket's last new slab.  Vectorised over the batch-bounded
    # range (never more than one slab per surviving edge).
    alive = k < total_new
    # owner bucket of each new-slab ordinal: searchsorted over the cumsum
    owner = jnp.searchsorted(cum, k, side="right")
    owner = jnp.clip(owner, 0, nb - 1).astype(jnp.int32)
    is_last = k == (ord_base[owner] + new_slabs[owner] - 1)
    tgt = jnp.where(is_last, INVALID_SLAB, slab_at(k + 1))
    write_at = jnp.where(alive, alloc_ids, g.capacity_slabs)
    next_slab = next_slab.at[write_at].set(tgt, mode="drop")
    slab_vertex = g.slab_vertex.at[write_at].set(
        g.bucket_vertex[owner], mode="drop")
    slab_new = g.slab_new.at[write_at].set(True, mode="drop")

    # --- tails ----------------------------------------------------------------
    new_tail = jnp.where(has_new, slab_at(cum - 1), tail)
    new_fill = jnp.where(has_new,
                         overflow - (new_slabs - 1) * SLAB_WIDTH,
                         fill + counts)

    # --- UpdateIterator bookkeeping (first insert of the epoch per bucket) ---
    got = counts > 0
    first_time = got & ~g.upd_flag
    # first new element lands in the tail slab (if it had room) else in the
    # first freshly allocated slab at lane 0.
    f_slab = jnp.where(room > 0, tail, slab_at(ord_base))
    f_lane = jnp.where(room > 0, fill, 0)
    upd_flag = g.upd_flag | got
    upd_slab = jnp.where(first_time, f_slab, g.upd_slab)
    upd_lane = jnp.where(first_time, f_lane, g.upd_lane)

    # --- degrees --------------------------------------------------------------
    src_seg = jnp.where(new, src_s.astype(jnp.int32), g.n_vertices)
    deg_inc = jax.ops.segment_sum(new.astype(jnp.int32), src_seg,
                                  num_segments=g.n_vertices + 1)[:g.n_vertices]

    inserted_sorted = new
    inserted = jnp.zeros((B,), dtype=bool).at[order].set(inserted_sorted)

    g2 = dataclasses.replace(
        g, keys=keys, weights=weights, next_slab=next_slab,
        slab_vertex=slab_vertex, tail_slab=new_tail, tail_fill=new_fill,
        upd_flag=upd_flag, upd_slab=upd_slab, upd_lane=upd_lane,
        next_free=g.next_free + total_new - take,
        free_top=g.free_top - take,
        slab_new=slab_new,
        degree=g.degree + deg_inc,
        n_edges=g.n_edges + jnp.sum(new.astype(jnp.int32)))
    return g2, inserted


# ----------------------------------------------------------------------------
# delete
# ----------------------------------------------------------------------------

def delete_edges_ref(g: SlabGraph, src: jnp.ndarray, dst: jnp.ndarray
                     ) -> Tuple[SlabGraph, jnp.ndarray]:
    """Batched ``DeleteEdgeBatch``: flip found lanes to TOMBSTONE (paper §6:
    "the deletion operation only flips a valid entry to TOMBSTONE_KEY")."""
    src = src.astype(jnp.uint32)
    dst = dst.astype(jnp.uint32)
    B = src.shape[0]
    valid = batch_valid(g, src, dst)

    b = edge_buckets(g, src, dst, valid)
    order, b_s = sort_by_bucket(b, dst, valid)
    dst_s, src_s, valid_s = dst[order], src[order], valid[order]
    same_prev = jnp.zeros((B,), dtype=bool)
    if B > 1:
        same_prev = same_prev.at[1:].set(
            (b_s[1:] == b_s[:-1]) & (dst_s[1:] == dst_s[:-1]))
    cand = valid_s & ~same_prev

    found, slab, lane = probe(g, jnp.where(cand, b_s, 0), dst_s, cand)
    hit = found & cand

    wslab = jnp.where(hit, slab, g.capacity_slabs)
    wlane = jnp.where(hit, lane, 0)
    keys = g.keys.at[wslab, wlane].set(TOMBSTONE_KEY, mode="drop")

    src_seg = jnp.where(hit, src_s.astype(jnp.int32), g.n_vertices)
    deg_dec = jax.ops.segment_sum(hit.astype(jnp.int32), src_seg,
                                  num_segments=g.n_vertices + 1)[:g.n_vertices]

    deleted = jnp.zeros((B,), dtype=bool).at[order].set(hit)
    g2 = dataclasses.replace(
        g, keys=keys, degree=g.degree - deg_dec,
        n_edges=g.n_edges - jnp.sum(hit.astype(jnp.int32)))
    return g2, deleted

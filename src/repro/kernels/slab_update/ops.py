"""Dispatch + engine layer for the slab-update plane.

The paper's headline wins over Hornet are on the *update* plane (12.94×
insert, 6.1× delete, 12.6× query) — this module makes batched mutation a
first-class fused engine instead of a chain of generic XLA ops.  Four
things distinguish the engine from the ``ref.py`` oracle it reproduces
bit-for-bit:

1. **Run-local placement.**  The oracle plans placement with per-*bucket*
   arrays — ``segment_sum`` over ``n_buckets`` segments, ``n_buckets``-sized
   cumsum/searchsorted/where updates, an O(V) degree ``segment_sum`` — all
   O(pool) work for an O(batch) mutation.  The engine plans over the sorted
   batch's *runs* (one run per touched bucket, ≤ B of them): counts, room,
   overflow, and new-slab bases are computed per run and scattered back, so
   every planning step is O(B log B).

2. **In-place commit via donation.**  All entry points accept
   ``donate=True`` (and ``apply_update`` / ``update_views`` default to it):
   the graph's pooled buffers are donated into the jit boundary, so the
   key/weight/degree scatters mutate storage in place — the TPU translation
   of Meerkat's in-place slab writes.  A donated graph must not be reused by
   the caller afterwards (move semantics, like the GPU original).

3. **Pallas probe/commit kernels** (``impl="pallas"``): the tiled chain-walk
   probe terminates per batch-tile instead of per whole batch.  The fused
   commit kernel (keys+weights+degrees in one aliased pass) is opt-in via
   ``use_commit_kernel=True``: its per-lane loop serializes within a grid
   step, so the default commit is the vectorized XLA scatter — already
   in-place under donation — until a tiled commit lowering proves faster.

4. **Packed probe** (``probe_packed``, the XLA engine's chain walk): as a
   batch's lanes find their keys or reach their chains' ends, the lanes
   still walking are packed into loops half as wide, so one long hub chain
   no longer makes every lane of the batch gather a row per trip.

Implementation selection (``impl``):

* ``"pallas"`` — probe/commit Pallas kernels, interpret mode only (for
  validation): the TPU v5e compiler refuses the probe's ``keys_ref[idx]``
  gather from an ``ANY`` ref (``Cannot do int indexing on TPU``) and the
  commit kernel's loads outside VMEM/SMEM;
* ``"jnp"``    — the run-local engine lowered through XLA scatters;
* ``"oracle"`` — the original whole-pool path (``ref.py``), bit-exact
  reference;
* ``"auto"``   — ``"jnp"`` on every backend (``repro.kernels.resolve_impl``).

All three produce bit-identical graphs and masks (tests/test_slab_update.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.hashing import (INVALID_SLAB, INVALID_VERTEX, SLAB_WIDTH,
                             TOMBSTONE_KEY)
from ...core.slab_graph import SlabGraph
from ... import obs
from ...obs import timed_dispatch
from .. import resolve_impl
from .kernel import slab_commit_pallas, slab_probe_pallas
from .ref import (ProbeCount, batch_valid, delete_edges_ref, edge_buckets,
                  insert_edges_ref, query_edges_ref)

IMPLS = ("auto", "pallas", "jnp", "oracle")

# View roles understood by the stacked multi-view plane (update_views).
FORWARD = "forward"
TRANSPOSE = "transpose"
SYMMETRIC = "symmetric"

_STATIC = ("impl", "interpret", "queries_per_tile", "use_commit_kernel")


def count_probes(probes) -> None:
    """``probe.<loop>.{hops,visits,width,rows,packs}`` counters on the
    innermost open ``obs`` span, from a dict of ``ProbeCount`` (None: the
    impl counts nothing); a no-op with tracing off.  The device values ride
    the span's one read at its exit (or its ``fetch``)."""
    if not obs.trace.enabled():
        return
    for key, pc in probes.items():
        if pc is not None:
            obs.count(f"probe.{key}.hops", pc.hops)
            obs.count(f"probe.{key}.visits", pc.visits)
            obs.count(f"probe.{key}.width", pc.width)
            obs.count(f"probe.{key}.rows", pc.rows)
            obs.count(f"probe.{key}.packs", pc.packs)


def _copy_aliased(tree):
    """Copy leaves that appear more than once in ``tree`` (by object id).

    Donation rejects the same buffer appearing twice in one call, and the
    SlabGraph legitimately aliases small fields (``update_slab_pointers``
    repositions ``upd_slab``/``upd_lane`` onto the tail arrays, and
    ``epoch_next_free`` onto ``next_free``).  Those aliases are always the
    small per-bucket/scalar arrays, so breaking them with a copy is cheap —
    the pools are never aliased.
    """
    seen = set()

    def visit(x):
        if isinstance(x, jax.Array):
            if id(x) in seen:
                return x.copy()
            seen.add(id(x))
        return x

    return jax.tree_util.tree_map(visit, tree)


def _resolve(impl: str, interpret: Optional[bool]):
    return resolve_impl(impl, interpret, xla="jnp",
                        impls=("pallas", "jnp", "oracle"))


#: the narrowest stage of ``probe_packed``: below it a trip's fixed cost
#: outweighs the rows it gathers
PACK_FLOOR = 128


def _stage_widths(B: int) -> Tuple[int, ...]:
    """``probe_packed``'s stage widths: B, B/2, B/4, ... down to
    ``PACK_FLOOR`` lanes; one stage for a batch of ``PACK_FLOOR`` or
    fewer."""
    widths = [B]
    while widths[-1] // 2 >= PACK_FLOOR:
        widths.append(widths[-1] // 2)
    return tuple(widths)


def probe_packed(g: SlabGraph, bucket, dst, valid):
    """``ref.probe_counted``'s (found, slab, lane) and ``ProbeCount``, with
    finished lanes retired from the loop.

    Stage k walks ``w_k`` lanes (``_stage_widths``) until none is live or
    at most ``w_{k+1}`` are, then packs the live ones into the first
    ``w_{k+1}`` slots (a prefix sum of the live mask places them) and
    hands them to stage k+1.  A pack, and every later stage, is skipped
    once no lane is live.  Each lane's walk is the oracle's, so every
    result and ``hops``/``visits`` equal its; ``rows`` counts the
    ``w_k`` rows each trip of stage k gathers."""
    B = bucket.shape[0]
    widths = _stage_widths(B)
    dst = dst.astype(jnp.uint32)
    cur = jnp.where(valid, bucket, INVALID_SLAB).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def stage(k, idx, cur, dst, n, out, tally):
        # idx: each slot's batch position (B for an empty slot; None at
        # stage 0, where slot and position coincide)
        w = widths[k]
        last = k + 1 == len(widths)
        stop = 0 if last else widths[k + 1]

        def trip(state):
            cur, slab, lane, trips, visits, n = state
            live = cur != INVALID_SLAB
            rows = g.keys[jnp.maximum(cur, 0)]                 # (w, 128)
            hit = (rows == dst[:, None]) & live[:, None]
            hit_any = jnp.any(hit, axis=1)
            slab = jnp.where(hit_any, cur, slab)
            lane = jnp.where(hit_any,
                             jnp.argmax(hit, axis=1).astype(jnp.int32), lane)
            nxt = g.next_slab[jnp.maximum(cur, 0)]
            cur = jnp.where(live & ~hit_any, nxt, INVALID_SLAB)
            return (cur, slab, lane, trips + 1, visits + n,
                    jnp.sum((cur != INVALID_SLAB).astype(jnp.int32)))

        cur, slab, lane, trips, visits, n = jax.lax.while_loop(
            lambda s: s[-1] > stop, trip,
            (cur, jnp.full((w,), INVALID_SLAB, jnp.int32),
             jnp.full((w,), -1, jnp.int32), zero, zero, n))
        if idx is None:
            out = (slab, lane)
        else:
            out = (out[0].at[idx].set(slab, mode="drop"),
                   out[1].at[idx].set(lane, mode="drop"))
        hops, vis, rows, packs = tally
        tally = (hops + trips, vis + visits, rows + w * trips, packs)
        if last:
            return out, tally

        def pack():
            w2 = widths[k + 1]
            live = cur != INVALID_SLAB
            pos = jnp.cumsum(live.astype(jnp.int32)) - 1
            src = jnp.full((w2,), w, jnp.int32).at[
                jnp.where(live, pos, w2)].set(
                    jnp.arange(w, dtype=jnp.int32), mode="drop")
            used = src < w
            src = jnp.minimum(src, w - 1)
            at = src if idx is None else idx[src]
            return stage(k + 1, jnp.where(used, at, B),
                         jnp.where(used, cur[src], INVALID_SLAB), dst[src],
                         n, out, tally[:3] + (tally[3] + 1,))

        return jax.lax.cond(n > 0, pack, lambda: (out, tally))

    n0 = jnp.sum((cur != INVALID_SLAB).astype(jnp.int32))
    (slab, lane), (hops, visits, rows, packs) = stage(
        0, None, cur, dst, n0, None, (zero, zero, zero, zero))
    return slab != INVALID_SLAB, slab, lane, ProbeCount(
        hops=hops, visits=visits, width=B, rows=rows, packs=packs)


def _probe_dispatch(g, bucket, dst, valid, *, impl, interpret, qpt):
    """(found, slab, lane, ProbeCount | None): the Pallas probe reports no
    count."""
    if impl == "pallas":
        start = jnp.where(valid, bucket, INVALID_SLAB).astype(jnp.int32)
        return (*slab_probe_pallas(g.keys, g.next_slab, start, dst,
                                   queries_per_tile=qpt,
                                   interpret=interpret), None)
    return probe_packed(g, bucket, dst, valid)


def _classify(g, src, dst, *, impl, interpret, qpt):
    """Shared front half: hash → one variadic stable sort → dup-collapse →
    chain-walk probe, all on the sorted batch (and the probe's count)."""
    B = src.shape[0]
    valid = batch_valid(g, src, dst)
    b = edge_buckets(g, src, dst, valid)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    b_key = jnp.where(valid, b, big)
    iota = jnp.arange(B, dtype=jnp.int32)
    # one fused variadic sort — same permutation as the oracle's lexsort
    # (stable on (bucket, dst), pads pushed to the end)
    b_s, _, order = jax.lax.sort((b_key, dst.astype(jnp.int32), iota),
                                 num_keys=2, is_stable=True)
    dst_s, src_s, valid_s = dst[order], src[order], valid[order]
    same_prev = jnp.zeros((B,), dtype=bool)
    if B > 1:
        same_prev = same_prev.at[1:].set(
            (b_s[1:] == b_s[:-1]) & (dst_s[1:] == dst_s[:-1]))
    cand = valid_s & ~same_prev
    found, slab, lane, count = _probe_dispatch(
        g, b_s, dst_s, cand, impl=impl, interpret=interpret, qpt=qpt)
    return order, b_s, src_s, dst_s, cand, found, slab, lane, count


# ----------------------------------------------------------------------------
# engine bodies (traced; jitted by the public entry points below).  The
# counted bodies also return their probe loop's ``ProbeCount`` (None for the
# oracle and Pallas impls): ``_query_body``, ``_update_views_body`` (named
# as the programs a device trace shows) and ``_*_body_counted``; the other
# bodies drop it.
# ----------------------------------------------------------------------------

def _query_body(g, src, dst, *, impl="auto", interpret=None,
                queries_per_tile=256, use_commit_kernel=False):
    del use_commit_kernel                       # queries never commit
    impl, interpret = _resolve(impl, interpret)
    src = src.astype(jnp.uint32)
    dst = dst.astype(jnp.uint32)
    if impl == "oracle":
        return query_edges_ref(g, src, dst), None
    valid = batch_valid(g, src, dst)
    b = edge_buckets(g, src, dst, valid)
    found, _, _, count = _probe_dispatch(
        g, b, dst, valid, impl=impl, interpret=interpret,
        qpt=queries_per_tile)
    return found & valid, count


def _query_found(g, src, dst, **kw):
    return _query_body(g, src, dst, **kw)[0]


def _insert_body_counted(g, src, dst, w=None, *, impl="auto",
                         interpret=None, queries_per_tile=256,
                         use_commit_kernel=False):
    impl, interpret = _resolve(impl, interpret)
    src = src.astype(jnp.uint32)
    dst = dst.astype(jnp.uint32)
    if impl == "oracle":
        return (*insert_edges_ref(g, src, dst, w), None)
    B = src.shape[0]
    W = SLAB_WIDTH
    nb = g.n_buckets
    cap = g.capacity_slabs

    order, b_s, src_s, dst_s, cand, exists, _, _, count = _classify(
        g, src, dst, impl=impl, interpret=interpret, qpt=queries_per_tile)
    w_s = None if w is None else w[order]
    new = cand & ~exists

    # --- per-lane rank within the bucket run (identical to the oracle) ------
    excl = jnp.cumsum(new.astype(jnp.int32)) - new.astype(jnp.int32)
    run_start = jnp.ones((B,), dtype=bool)
    if B > 1:
        run_start = run_start.at[1:].set(b_s[1:] != b_s[:-1])
    base = jax.lax.cummax(jnp.where(run_start, excl, -1))
    rank = jnp.where(new, excl - base, 0)

    # --- run-local placement plan: one run per touched bucket, ≤ B runs -----
    run_id = jnp.cumsum(run_start.astype(jnp.int32)) - 1          # (B,)
    count_r = jax.ops.segment_sum(new.astype(jnp.int32), run_id,
                                  num_segments=B)                 # (B,)
    bucket_r = jax.ops.segment_max(b_s, run_id, num_segments=B)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    run_ok = (bucket_r >= 0) & (bucket_r < big)     # real (non-pad) buckets
    b_safe_r = jnp.where(run_ok, bucket_r, 0)
    tail_r = g.tail_slab[b_safe_r]
    fill_r = g.tail_fill[b_safe_r]
    room_r = W - fill_r
    overflow_r = jnp.maximum(count_r - room_r, 0)
    new_slabs_r = (overflow_r + W - 1) // W
    cum_r = jnp.cumsum(new_slabs_r)
    total_new = cum_r[-1]

    # --- allocation: drain the free-slab recycling list, then bump ----------
    # Ordinal o of this call's o-th new slab resolves to a recycled slab
    # (popped from the top of the free list — the SlabAlloc reuse path) while
    # any remain, else to the bump allocator.  Identical in the oracle.
    k = jnp.arange(B, dtype=jnp.int32)
    take = jnp.minimum(total_new, g.free_top)
    recycled = g.free_list[jnp.clip(g.free_top - 1 - k, 0, cap - 1)]
    alloc_ids = jnp.where(k < take, recycled, g.next_free + k - take)
    ord_base_r = cum_r - new_slabs_r                # run's first slab ordinal

    def slab_at(ordinal):
        return alloc_ids[jnp.clip(ordinal, 0, B - 1)]

    e_room = room_r[run_id]
    in_tail = rank < e_room
    e_slab = jnp.where(in_tail, tail_r[run_id],
                       slab_at(ord_base_r[run_id] + (rank - e_room) // W))
    e_lane = jnp.where(in_tail, fill_r[run_id] + rank, (rank - e_room) % W)
    e_slab = jnp.where(new, e_slab, cap)            # park rejects (dropped)
    e_lane = jnp.where(new, e_lane, 0)

    # --- fused commit: key/weight scatter + degree update -------------------
    # The aliased commit kernel is opt-in: its per-lane RMW loop serializes
    # within one grid step, while the XLA scatter is vectorized and already
    # commits in place inside a donated jit.
    deg_idx = jnp.where(new, src_s.astype(jnp.int32), g.n_vertices)
    if impl == "pallas" and use_commit_kernel:
        keys, degree, weights = slab_commit_pallas(
            g.keys, g.degree, g.weights if g.weighted else None,
            e_slab, e_lane, dst_s, deg_idx,
            jnp.ones((B,), jnp.int32), w_s, interpret=interpret)
        if not g.weighted:
            weights = g.weights
    else:
        keys = g.keys.at[e_slab, e_lane].set(dst_s, mode="drop")
        weights = g.weights
        if g.weighted:
            wv = (jnp.zeros((B,), jnp.float32) if w_s is None
                  else w_s.astype(jnp.float32))
            weights = g.weights.at[e_slab, e_lane].set(wv, mode="drop")
        degree = g.degree.at[deg_idx].add(1, mode="drop")

    # --- chain the freshly allocated slabs (run-local, ≤ B of them) ---------
    # Allocated ids are no longer contiguous (recycled slabs interleave with
    # bump-allocated ones), so links resolve ordinals through ``alloc_ids``.
    has_new_r = new_slabs_r > 0
    link_from_r = jnp.where(has_new_r, tail_r, cap)
    next_slab = g.next_slab.at[link_from_r].set(slab_at(ord_base_r),
                                                mode="drop")
    alive = k < total_new
    owner = jnp.searchsorted(cum_r, k, side="right")
    owner = jnp.clip(owner, 0, B - 1).astype(jnp.int32)
    is_last = k == (ord_base_r[owner] + new_slabs_r[owner] - 1)
    tgt = jnp.where(is_last, INVALID_SLAB, slab_at(k + 1))
    write_at = jnp.where(alive, alloc_ids, cap)
    next_slab = next_slab.at[write_at].set(tgt, mode="drop")
    slab_vertex = g.slab_vertex.at[write_at].set(
        g.bucket_vertex[b_safe_r[owner]], mode="drop")
    slab_new = g.slab_new.at[write_at].set(True, mode="drop")

    # --- tails + UpdateIterator state: scatter at the touched buckets only --
    wb_r = jnp.where(run_ok, bucket_r, nb)          # index nb → dropped
    new_tail_r = jnp.where(has_new_r, slab_at(cum_r - 1), tail_r)
    new_fill_r = jnp.where(has_new_r, overflow_r - (new_slabs_r - 1) * W,
                           fill_r + count_r)
    tail_slab = g.tail_slab.at[wb_r].set(new_tail_r, mode="drop")
    tail_fill = g.tail_fill.at[wb_r].set(new_fill_r, mode="drop")

    got_r = count_r > 0
    first_r = got_r & ~g.upd_flag[b_safe_r]
    f_slab_r = jnp.where(room_r > 0, tail_r, slab_at(ord_base_r))
    f_lane_r = jnp.where(room_r > 0, fill_r, 0)
    upd_flag = g.upd_flag.at[jnp.where(got_r, bucket_r, nb)].set(
        True, mode="drop")
    upd_slab = g.upd_slab.at[jnp.where(first_r, bucket_r, nb)].set(
        f_slab_r, mode="drop")
    upd_lane = g.upd_lane.at[jnp.where(first_r, bucket_r, nb)].set(
        f_lane_r, mode="drop")

    inserted = jnp.zeros((B,), dtype=bool).at[order].set(new)
    g2 = dataclasses.replace(
        g, keys=keys, weights=weights, next_slab=next_slab,
        slab_vertex=slab_vertex, tail_slab=tail_slab, tail_fill=tail_fill,
        upd_flag=upd_flag, upd_slab=upd_slab, upd_lane=upd_lane,
        next_free=g.next_free + total_new - take,
        free_top=g.free_top - take,
        slab_new=slab_new,
        degree=degree,
        n_edges=g.n_edges + jnp.sum(new.astype(jnp.int32)))
    return g2, inserted, count


def _insert_body(g, src, dst, w=None, **kw):
    return _insert_body_counted(g, src, dst, w, **kw)[:2]


def _delete_body_counted(g, src, dst, *, impl="auto", interpret=None,
                         queries_per_tile=256, use_commit_kernel=False):
    impl, interpret = _resolve(impl, interpret)
    src = src.astype(jnp.uint32)
    dst = dst.astype(jnp.uint32)
    if impl == "oracle":
        return (*delete_edges_ref(g, src, dst), None)
    B = src.shape[0]

    order, b_s, src_s, dst_s, cand, found, slab, lane, count = _classify(
        g, src, dst, impl=impl, interpret=interpret, qpt=queries_per_tile)
    hit = found & cand

    wslab = jnp.where(hit, slab, g.capacity_slabs)
    wlane = jnp.where(hit, lane, 0)
    deg_idx = jnp.where(hit, src_s.astype(jnp.int32), g.n_vertices)
    if impl == "pallas" and use_commit_kernel:
        keys, degree, _ = slab_commit_pallas(
            g.keys, g.degree, None, wslab, wlane,
            jnp.full((B,), TOMBSTONE_KEY, jnp.uint32), deg_idx,
            jnp.full((B,), -1, jnp.int32), interpret=interpret)
    else:
        keys = g.keys.at[wslab, wlane].set(TOMBSTONE_KEY, mode="drop")
        degree = g.degree.at[deg_idx].add(-1, mode="drop")

    deleted = jnp.zeros((B,), dtype=bool).at[order].set(hit)
    g2 = dataclasses.replace(
        g, keys=keys, degree=degree,
        n_edges=g.n_edges - jnp.sum(hit.astype(jnp.int32)))
    return g2, deleted, count


def _delete_body(g, src, dst, **kw):
    return _delete_body_counted(g, src, dst, **kw)[:2]


# ----------------------------------------------------------------------------
# public entry points (jit'd; optional buffer donation)
# ----------------------------------------------------------------------------

_query_jit = jax.jit(_query_body, static_argnames=_STATIC)
_insert_jit = jax.jit(_insert_body, static_argnames=_STATIC)
_insert_jit_don = jax.jit(_insert_body, static_argnames=_STATIC,
                          donate_argnums=(0,))
_delete_jit = jax.jit(_delete_body, static_argnames=_STATIC)
_delete_jit_don = jax.jit(_delete_body, static_argnames=_STATIC,
                          donate_argnums=(0,))


@timed_dispatch("slab_update", op="query_edges")
def _query_edges(g: SlabGraph, src, dst, *, impl: str = "auto",
                 interpret: Optional[bool] = None,
                 queries_per_tile: int = 256,
                 use_commit_kernel: bool = False
                 ) -> Tuple[jnp.ndarray, Optional[ProbeCount]]:
    """``query_edges`` and its probe loop's ``ProbeCount`` (None for the
    oracle and Pallas impls): the same compiled program, so reading the
    count or not never recompiles."""
    return _query_jit(g, src, dst, impl=impl, interpret=interpret,
                      queries_per_tile=queries_per_tile,
                      use_commit_kernel=use_commit_kernel)


def query_edges(g: SlabGraph, src, dst, **kw) -> jnp.ndarray:
    """Batched membership query (paper's query benchmark, Fig. 5).

    Lanes with out-of-range src or sentinel (EMPTY/TOMBSTONE/INVALID) dst
    return False instead of probing with a garbage key.  Keywords as
    ``_query_edges`` (``use_commit_kernel`` is accepted for
    engine-kwarg uniformity; queries never commit).  With tracing on, the
    probe's count rides the caller's span as ``probe.query.*``.
    """
    found, count = _query_edges(g, src, dst, **kw)
    count_probes({"query": count})
    return found


@timed_dispatch("slab_update")
def insert_edges(g: SlabGraph, src, dst, w=None, *, impl: str = "auto",
                 interpret: Optional[bool] = None,
                 queries_per_tile: int = 256,
                 use_commit_kernel: bool = False,
                 donate: bool = False) -> Tuple[SlabGraph, jnp.ndarray]:
    """Batched ``InsertEdgeBatch`` through the engine (see module doc).

    ``donate=True`` consumes ``g``'s buffers (in-place commit — the caller
    must thread the returned graph and never touch ``g`` again).
    ``use_commit_kernel`` routes the pallas impl's commit through the
    aliased single-pass kernel instead of the default vectorized scatter.
    """
    fn = _insert_jit_don if donate else _insert_jit
    if donate:
        g = _copy_aliased(g)
    return fn(g, src, dst, w, impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)


@timed_dispatch("slab_update")
def delete_edges(g: SlabGraph, src, dst, *, impl: str = "auto",
                 interpret: Optional[bool] = None,
                 queries_per_tile: int = 256,
                 use_commit_kernel: bool = False,
                 donate: bool = False) -> Tuple[SlabGraph, jnp.ndarray]:
    """Batched ``DeleteEdgeBatch`` through the engine (tombstone flip)."""
    fn = _delete_jit_don if donate else _delete_jit
    if donate:
        g = _copy_aliased(g)
    return fn(g, src, dst, impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)


# ----------------------------------------------------------------------------
# fused mixed batch: delete-then-insert in ONE dispatch
# ----------------------------------------------------------------------------

def _apply_update_body(g, ins, dels, *, impl="auto", interpret=None,
                       queries_per_tile=256, use_commit_kernel=False):
    kw = dict(impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)
    ins_mask = del_mask = None
    if dels is not None:
        g, del_mask = _delete_body(g, dels[0], dels[1], **kw)
    if ins is not None:
        g, ins_mask = _insert_body(g, ins[0], ins[1], ins[2], **kw)
    return g, ins_mask, del_mask


_apply_jit = jax.jit(_apply_update_body, static_argnames=_STATIC)
_apply_jit_don = jax.jit(_apply_update_body, static_argnames=_STATIC,
                         donate_argnums=(0,))


@timed_dispatch("slab_update")
def apply_update(g: SlabGraph, ins_src=None, ins_dst=None, ins_w=None,
                 del_src=None, del_dst=None, *, impl: str = "auto",
                 interpret: Optional[bool] = None,
                 queries_per_tile: int = 256,
                 use_commit_kernel: bool = False, donate: bool = True
                 ) -> Tuple[SlabGraph, Optional[jnp.ndarray],
                            Optional[jnp.ndarray]]:
    """One mixed update epoch — deletes apply before inserts, one jit call.

    The streaming inner loop: donation is ON by default, so the pool mutates
    in place and the caller must thread the returned graph.  Returns
    ``(graph, inserted_mask | None, deleted_mask | None)``.
    """
    ins = None if ins_src is None else (ins_src, ins_dst, ins_w)
    dels = None if del_src is None else (del_src, del_dst)
    fn = _apply_jit_don if donate else _apply_jit
    if donate:
        g = _copy_aliased(g)
    return fn(g, ins, dels, impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)


# ----------------------------------------------------------------------------
# stacked shard plane: one fused dispatch over a leading shard dim
# ----------------------------------------------------------------------------

def _update_shards_body(graphs, ins, dels, *, impl="auto", interpret=None,
                        queries_per_tile=256, use_commit_kernel=False):
    kw = dict(impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)

    def one(g, i, d):
        return _apply_update_body(g, i, d, **kw)

    return jax.vmap(one)(graphs, ins, dels)


def _query_shards_body(graphs, src, dst, *, impl="auto", interpret=None,
                       queries_per_tile=256, use_commit_kernel=False):
    del use_commit_kernel
    kw = dict(impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile)
    return jax.vmap(lambda g, s, d: _query_found(g, s, d, **kw))(
        graphs, src, dst)


_shards_jit = jax.jit(_update_shards_body, static_argnames=_STATIC)
_shards_jit_don = jax.jit(_update_shards_body, static_argnames=_STATIC,
                          donate_argnums=(0,))
_qshards_jit = jax.jit(_query_shards_body, static_argnames=_STATIC)


@timed_dispatch("slab_update")
def update_shards(graphs, ins=None, dels=None, *, impl: str = "auto",
                  interpret: Optional[bool] = None,
                  queries_per_tile: int = 256,
                  use_commit_kernel: bool = False, donate: bool = False):
    """One mixed update epoch on a SHARD-STACKED graph — the engine body
    vmapped over the leading shard dim, one dispatch for every shard.

    ``graphs`` is a SlabGraph whose data leaves carry a leading shard dim
    (``distributed.sharded_graph.shard_empty``); ``ins`` is
    ``(src, dst, w | None)`` and ``dels`` is ``(src, dst)``, each
    ``(n_shards, cap)`` owner-routed per-shard batches (INVALID padding,
    src shard-local, dst global).  Deletes apply before inserts.  Returns
    ``(graphs, inserted_mask | None, deleted_mask | None)`` with
    ``(n_shards, cap)`` masks.  ``donate=True`` consumes the stacked pools
    (in-place mutation; thread the returned graphs).
    """
    fn = _shards_jit_don if donate else _shards_jit
    if donate:
        graphs = _copy_aliased(graphs)
    return fn(graphs, ins, dels, impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)


@timed_dispatch("slab_update")
def query_shards(graphs, src, dst, *, impl: str = "auto",
                 interpret: Optional[bool] = None,
                 queries_per_tile: int = 256) -> jnp.ndarray:
    """Batched membership over a shard-stacked graph: (n_shards, cap)
    owner-routed queries → (n_shards, cap) found mask, one dispatch."""
    return _qshards_jit(graphs, src, dst, impl=impl, interpret=interpret,
                        queries_per_tile=queries_per_tile)


# ----------------------------------------------------------------------------
# stacked multi-view plane: every GraphStore view in ONE dispatch
# ----------------------------------------------------------------------------

def _update_views_body(views, ins, dels, *, roles, impl="auto",
                       interpret=None, queries_per_tile=256,
                       use_commit_kernel=False):
    kw = dict(impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)
    views = list(views)
    fidx = roles.index(FORWARD)
    ins_mask = del_mask = None
    probes = {}

    if dels is not None:
        ds, dd = dels
        # forward first: the symmetric union consults the post-delete
        # forward view to decide whether the reverse direction survives.
        views[fidx], del_mask, probes[f"{FORWARD}.delete"] = \
            _delete_body_counted(views[fidx], ds, dd, **kw)
        for i, role in enumerate(roles):
            if i == fidx:
                continue
            if role == TRANSPOSE:
                views[i], _, probes[f"{role}.delete"] = \
                    _delete_body_counted(views[i], dd, ds, **kw)
            elif role == SYMMETRIC:
                rev, probes[f"{role}.reverse"] = _query_body(
                    views[fidx], dd, ds, **kw)
                gone = ~rev
                s2 = jnp.concatenate([jnp.where(gone, ds, INVALID_VERTEX),
                                      jnp.where(gone, dd, INVALID_VERTEX)])
                d2 = jnp.concatenate([dd, ds])
                views[i], _, probes[f"{role}.delete"] = \
                    _delete_body_counted(views[i], s2, d2, **kw)

    if ins is not None:
        s, d, w = ins
        views[fidx], ins_mask, probes[f"{FORWARD}.insert"] = \
            _insert_body_counted(views[fidx], s, d, w, **kw)
        for i, role in enumerate(roles):
            if i == fidx:
                continue
            if role == TRANSPOSE:
                views[i], _, probes[f"{role}.insert"] = \
                    _insert_body_counted(views[i], d, s, w, **kw)
            elif role == SYMMETRIC:
                w2 = None if w is None else jnp.concatenate([w, w])
                views[i], _, probes[f"{role}.insert"] = \
                    _insert_body_counted(
                        views[i], jnp.concatenate([s, d]),
                        jnp.concatenate([d, s]), w2, **kw)

    return tuple(views), ins_mask, del_mask, probes


_VIEWS_STATIC = ("roles",) + _STATIC
_views_jit = jax.jit(_update_views_body, static_argnames=_VIEWS_STATIC)
_views_jit_don = jax.jit(_update_views_body, static_argnames=_VIEWS_STATIC,
                         donate_argnums=(0,))


@timed_dispatch("slab_update", op="update_views")
def _update_views(views: Tuple[SlabGraph, ...], roles: Tuple[str, ...],
                  ins=None, dels=None, *, impl: str = "auto",
                  interpret: Optional[bool] = None,
                  queries_per_tile: int = 256,
                  use_commit_kernel: bool = False, donate: bool = True):
    """``update_views`` plus the ``ProbeCount`` of every probe loop, keyed
    ``<role>.<op>``: ``forward``/``transpose``/``symmetric`` ×
    ``delete``/``insert``, and ``symmetric.reverse`` for the symmetric
    view's reverse-existence query (a value is None where the impl reports
    no count).  Returns ``(new_views, inserted_mask, deleted_mask,
    probes)``; one compiled program with ``update_views``."""
    if FORWARD not in roles:
        raise ValueError("update_views requires a forward view")
    fn = _views_jit_don if donate else _views_jit
    if donate:
        views = _copy_aliased(views)
    return fn(views, ins, dels, roles=tuple(roles), impl=impl,
              interpret=interpret, queries_per_tile=queries_per_tile,
              use_commit_kernel=use_commit_kernel)


def update_views(views: Tuple[SlabGraph, ...], roles: Tuple[str, ...],
                 ins=None, dels=None, **kw):
    """Apply one canonical batch to every live view in a single dispatch.

    ``views`` / ``roles`` are parallel tuples; roles come from
    {FORWARD, TRANSPOSE, SYMMETRIC} and must include FORWARD.  The
    transpose and symmetric batches are *derived* from the canonical
    (src, dst) batch on device (swap / concat) — callers hash/dedup/pad
    exactly once.  ``ins`` is ``(src, dst, w | None)``, ``dels`` is
    ``(src, dst)``; deletes apply before inserts.  Returns
    ``(new_views, inserted_mask, deleted_mask)`` with masks over the
    forward view's canonical batch.  Keywords as ``_update_views``.
    With tracing on, every probe loop's count rides the caller's span as
    ``probe.<role>.<op>.*``.

    Donation is ON by default: every view's buffers are consumed and
    mutated in place — thread the returned views.
    """
    *out, probes = _update_views(views, roles, ins, dels, **kw)
    count_probes(probes)
    return tuple(out)


# ----------------------------------------------------------------------------
# shard_map compatibility (DESIGN.md §9)
#
# The traced engine bodies are safe to call INSIDE a ``shard_map`` body on a
# shard-local SlabGraph: they contain no host sync, no jit boundary, and no
# collective — every batch position is processed independently, INVALID
# padding rows sort last and scatter with ``mode="drop"``, so the resulting
# pools are a function of the valid edges' relative order only (not of the
# padding POSITIONS).  That padding-position independence is what makes the
# single-program sharded epoch (``stream/sharded_store.py``, which calls
# ``_query_body`` and the ``_*_body_counted`` bodies) bit-identical to the
# vmap fallback even though the all-to-all routed batches carry interior
# (not tail) padding.
# ----------------------------------------------------------------------------


__all__ = ["IMPLS", "FORWARD", "TRANSPOSE", "SYMMETRIC", "ProbeCount",
           "probe_packed",
           "query_edges", "insert_edges", "delete_edges",
           "apply_update", "update_views", "count_probes",
           "update_shards", "query_shards",
           "slab_probe_pallas", "slab_commit_pallas"]

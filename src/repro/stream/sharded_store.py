"""ShardedGraphStore — the versioned multi-view update plane, vertex-
partitioned across a device mesh (DESIGN.md §7).

The sharded rendering of ``GraphStore``: the forward, transposed, and
symmetric views are each a ``ShardedSlabGraph`` (stacked shard-local pools,
modulo vertex striping), kept consistent as ONE versioned unit.  Per
``apply(inserts, deletes)`` the contract is the unsharded store's, plus the
distribution rules:

  1. ONE host-side canonicalisation (``canonical_batch`` — shared with the
     unsharded store), then per-view owner routing and per-shard dispatch
     happen inside ONE donated jit: forward routes by ``owner(src)``,
     transpose by ``owner(dst)``, the symmetric union by each direction's
     own source — the per-view routing steps are the only global exchanges
     of the epoch;
  2. routing buckets are sized on the host from the TRUE max per-owner run
     length (pow2-quantized, sticky across epochs — caps only ratchet up,
     reset at maintenance), so a skewed batch that lands entirely on one
     shard still routes every edge: overflow is impossible by
     construction, never silently dropped — and a drifting batch mix does
     not walk jit specialisations (``recompile_count`` tracks them);
  3. deletes before inserts; the symmetric union consults the post-delete
     forward view (a routed sharded query inside the same dispatch);
  4. every shard's pools mutate through the donated slab-update engine —
     the same fused kernel path the single-graph store uses, not the
     legacy per-op chain.  Two dispatch renderings, leaf-for-leaf
     identical: the stacked-``vmap`` fallback (runs anywhere), and the
     single-program ``shard_map`` epoch over the ("shard",) mesh
     (``place_on_mesh`` — per-shard routing + ``all_to_all`` bucket
     exchange, donated pools pinned to their devices; DESIGN.md §9);
  5. epochs close via ``update_slab_pointers`` on the stacked pools; the
     monotonic ``version``, bounded batch log, and listener protocol are
     identical to ``GraphStore`` — ``PropertyRegistry`` works unchanged;
  6. capacity headroom and analytics sweep bounds come from host-exact
     high-water accounting (``_high``/``sweep_rows``) — steady-state
     epochs never block on a device read.

Sharded ``stream_property`` hooks live here too (PageRank / WCC / BFS over
the sharded views via the slab-sweep engine's global-key sweeps).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..core.slab_graph import next_pow2, update_slab_pointers
from ..core.hashing import INVALID_VERTEX, SLAB_WIDTH
from ..core.worklist import EdgeFrontier, expand_vertices
from ..distributed.collectives import or_across_shards
from ..distributed.sharded_graph import (SHARD_AXIS, ShardedSlabGraph,
                                         _route_body, _scatter_back,
                                         ensure_capacity_sharded,
                                         bfs_sharded, graph_pspecs,
                                         max_owner_count, pagerank_sharded,
                                         reassemble_global, route_exchange,
                                         routing_cap, routing_cap_blocks,
                                         shard_from_edges_host, shard_slice,
                                         triangles_sharded, wcc_sharded)
from ..distributed.sharded_graph import place_on_mesh as _place_graph
from ..kernels.slab_update.ops import (ProbeCount, _copy_aliased,
                                       _delete_body_counted,
                                       _insert_body_counted, _query_body,
                                       count_probes)
from ..resilience import faults
from ..resilience.guard import run_with_retries, validate_batch
from .store import (ALL_VIEWS, FORWARD, SYMMETRIC, TRANSPOSE, AppliedBatch,
                    VersionedStoreBase, _FL_ADMIT, _FL_CLOSE, _FL_DISPATCH,
                    _FL_GROW, _FL_POST_WAL, _flight, _pad_f32, _pad_u32,
                    _pow2, canonical_batch, dedup_pairs)


# ----------------------------------------------------------------------------
# the fused multi-view sharded apply — route + mutate every view in ONE jit
# ----------------------------------------------------------------------------

def _across_shards(pc: Optional[ProbeCount], n_shards: int
                   ) -> Optional[ProbeCount]:
    """One probe loop's count over every shard, from per-shard (S,) counts
    of ``pc.width`` lanes each: the loop ends with its slowest shard, so
    ``hops`` is the most any shard made, over all S x width lanes; ``rows``
    sums the shards' gathers and ``packs`` is the most any shard made."""
    if pc is None:
        return None
    return ProbeCount(hops=jnp.max(pc.hops), visits=jnp.sum(pc.visits),
                      width=n_shards * pc.width, rows=jnp.sum(pc.rows),
                      packs=jnp.max(pc.packs))


def _sharded_apply_body(views, ins, dels, *, roles, n_shards, caps,
                        impl="auto", interpret=None, queries_per_tile=256):
    kw = dict(impl=impl, interpret=interpret,
              queries_per_tile=queries_per_tile, use_commit_kernel=False)
    fwd_del, tr_del, sym_del, fwd_ins, tr_ins, sym_ins = caps
    views = list(views)
    fidx = roles.index(FORWARD)
    ins_mask = del_mask = None
    probes = {}

    def vdel(sg, s, d, cap, key):
        bs, bd, _, origin, _ = _route_body(s, d, None, n_shards=n_shards,
                                           cap=cap)
        g, m, pc = jax.vmap(
            lambda g, a, b: _delete_body_counted(g, a, b, **kw))(
            sg.graphs, bs, bd)
        probes[key] = _across_shards(pc, n_shards)
        return dataclasses.replace(sg, graphs=g), m, origin

    def vins(sg, s, d, w, cap, key):
        bs, bd, bw, origin, _ = _route_body(s, d, w, n_shards=n_shards,
                                            cap=cap)
        g, m, pc = jax.vmap(
            lambda g, a, b, c: _insert_body_counted(g, a, b, c, **kw))(
            sg.graphs, bs, bd, bw)
        probes[key] = _across_shards(pc, n_shards)
        return dataclasses.replace(sg, graphs=g), m, origin

    if dels is not None:
        ds, dd = dels
        p = ds.shape[0]
        # forward first: the symmetric union consults the post-delete
        # forward view to decide whether the reverse direction survives.
        views[fidx], m, origin = vdel(views[fidx], ds, dd, fwd_del,
                                      f"{FORWARD}.delete")
        del_mask = _scatter_back(m, origin, p)
        for i, role in enumerate(roles):
            if i == fidx:
                continue
            if role == TRANSPOSE:
                views[i], _, _ = vdel(views[i], dd, ds, tr_del,
                                      f"{role}.delete")
            elif role == SYMMETRIC:
                bs, bd, _, qorig, _ = _route_body(dd, ds, None,
                                                  n_shards=n_shards,
                                                  cap=tr_del)
                found, pc = jax.vmap(lambda g, a, b: _query_body(
                    g, a, b, impl=impl, interpret=interpret,
                    queries_per_tile=queries_per_tile))(
                    views[fidx].graphs, bs, bd)
                probes[f"{role}.reverse"] = _across_shards(pc, n_shards)
                rev = _scatter_back(found, qorig, p)
                gone = ~rev
                s2 = jnp.concatenate([jnp.where(gone, ds, INVALID_VERTEX),
                                      jnp.where(gone, dd, INVALID_VERTEX)])
                d2 = jnp.concatenate([dd, ds])
                views[i], _, _ = vdel(views[i], s2, d2, sym_del,
                                      f"{role}.delete")

    if ins is not None:
        s, d, w = ins
        p = s.shape[0]
        views[fidx], m, origin = vins(views[fidx], s, d, w, fwd_ins,
                                      f"{FORWARD}.insert")
        ins_mask = _scatter_back(m, origin, p)
        for i, role in enumerate(roles):
            if i == fidx:
                continue
            if role == TRANSPOSE:
                views[i], _, _ = vins(views[i], d, s, w, tr_ins,
                                      f"{role}.insert")
            elif role == SYMMETRIC:
                w2 = None if w is None else jnp.concatenate([w, w])
                views[i], _, _ = vins(views[i], jnp.concatenate([s, d]),
                                      jnp.concatenate([d, s]), w2, sym_ins,
                                      f"{role}.insert")

    # epoch close folded into the same dispatch: update_slab_pointers is an
    # elementwise field replace, so running it on the stacked pools here
    # saves one jitted dispatch per view per epoch on the store hot path
    views = [dataclasses.replace(v, graphs=update_slab_pointers(v.graphs))
             for v in views]
    return tuple(views), ins_mask, del_mask, probes


_APPLY_STATIC = ("roles", "n_shards", "caps", "impl", "interpret",
                 "queries_per_tile")
_apply_jit_don = jax.jit(_sharded_apply_body, static_argnames=_APPLY_STATIC,
                         donate_argnums=(0,))


def _cap_rung(n: int) -> int:
    """Sticky-cap quantization: pow2 rungs up to 256, multiples of 256 past
    that.  The pure pow2 ladder wastes up to 2× engine batch width at large
    caps (a 1100-edge hot owner pays a 2048-wide bucket); the sticky ratchet
    already bounds how many rungs a drifting stream can visit, so finer
    rungs cost few extra specialisations."""
    if n <= 256:
        return next_pow2(n, lo=1)
    return -(-int(n) // 256) * 256


def _sym_concat_u32(a, b, p: int) -> np.ndarray:
    """Host (2p,) symmetric-candidate layout: the two halves each padded to
    ``p`` with INVALID — matching ``concatenate([pad(a), pad(b)])``, the
    exact batch the vmap body builds on device."""
    out = np.full(2 * p, INVALID_VERTEX, np.uint32)
    out[:len(a)] = a
    out[p:p + len(b)] = b
    return out


# ----------------------------------------------------------------------------
# the single-program epoch: the same multi-view route+mutate, but as ONE
# shard_map dispatch over the ("shard",) mesh (DESIGN.md §9).  Routing is a
# per-shard bucket sort + all_to_all exchange (1/S the sort work of the
# replicated vmap route), the one replicated value is the symmetric plane's
# reverse-existence mask (a psum), and the donated pools never leave their
# device.  Pool results are leaf-for-leaf identical to the vmap body.
# ----------------------------------------------------------------------------

def _sharded_apply_sm(views, dels, ins, *, roles, n_shards, caps, mesh,
                      impl="auto", interpret=None, queries_per_tile=256):
    """views: tuple of STACKED SlabGraph pytrees (one per role), placed
    under P("shard", ...).  Batches are (B,) device arrays with B a
    multiple of n_shards.  ``caps`` carries four (pair, total) cap tuples
    — forward/transpose × delete/insert — plus two plain symmetric totals:
    the symmetric plane needs no exchange of its own (it rides the forward
    and transpose exchanges, see below), only a compaction width."""
    kwq = dict(impl=impl, interpret=interpret,
               queries_per_tile=queries_per_tile)
    kw = dict(use_commit_kernel=False, **kwq)
    fwd_del, tr_del, sym_del, fwd_ins, tr_ins, sym_ins = caps
    fidx = roles.index(FORWARD)
    need_rev = len(roles) > 1
    # each probe loop's per-shard batch width, noted while the body traces
    widths = {}

    def _body(graphs_blk, dl, il):
        gs = [jax.tree.map(lambda x: x[0], g) for g in graphs_blk]
        ins_part = del_part = None
        counts = {}

        def note(key, out):
            # the shard's counts leave as (1,) rows of (S,) arrays
            *rest, pc = out
            if pc is not None:
                widths[key] = pc.width
                counts[key] = (pc.hops[None], pc.visits[None],
                               pc.rows[None], pc.packs[None])
            return rest[0] if len(rest) == 1 else tuple(rest)

        def route(s, d, w, cap):
            # two-level cap: route with per-(source block, owner) PAIR
            # buckets, then compact the received interior-padded
            # (S*cap_pair,) flatten down to the vmap bucket layout —
            # valid-first (stable sort -> global batch order preserved),
            # tail-padded to the per-owner TOTAL cap.  The engine batch is
            # then the same width as the vmap path bucket row and, under
            # skewed batches, ~S x smaller than the uncompacted flatten
            # (the pow2 pair caps inflate hard when one source block
            # concentrates on one owner).
            cap_pair, cap_tot = cap
            bs, bd, bw, orig, over = route_exchange(
                s, d, w, n_shards=n_shards, cap=cap_pair)
            if cap_tot < bs.shape[0]:
                perm = jnp.argsort(orig < 0, stable=True)[:cap_tot]
                bs, bd, orig = bs[perm], bd[perm], orig[perm]
                if bw is not None:
                    bw = bw[perm]
            return bs, bd, bw, orig, over

        def compact(cap_tot, s, d, w=None):
            # the symmetric ride-along concat is fwd_tot + tr_tot wide,
            # but the true per-owner candidate max — computed on host
            # from the (2B,) concat, exactly how the vmap path sizes its
            # own symmetric bucket — is often much smaller under skewed
            # batches, and the engine pays per batch column.  Valid-first
            # stable compaction preserves the global candidate order, so
            # the result is the vmap symmetric bucket leaf-for-leaf.
            # Under hub skew both candidate halves land on the same owner
            # and cap_tot ~= the concat width — there the sort costs more
            # than the saved columns, so only compact on a >= 2x width
            # reduction (the engine is padding-position independent, so
            # pools are identical either way).
            if cap_tot * 2 > s.shape[0]:
                return s, d, w
            perm = jnp.argsort(s == INVALID_VERTEX, stable=True)[:cap_tot]
            return s[perm], d[perm], None if w is None else w[perm]

        if dl is not None:
            ds_l, dd_l = dl
            n_del = ds_l.shape[0] * n_shards
            bs, bd, _, orig, _ = route(ds_l, dd_l, None, fwd_del)
            gs[fidx], m = note(f"{FORWARD}.delete", _delete_body_counted(
                gs[fidx], bs, bd, **kw))
            del_part = _scatter_back(m, orig, n_del)
            if need_rev:
                # ONE routed (dst, src) exchange feeds the transpose
                # delete, the reverse-existence query, AND (below) the
                # reverse half of the symmetric delete
                rbs, rbd, _, rorig, _ = route(dd_l, ds_l, None, tr_del)
            for i, role in enumerate(roles):
                if i == fidx:
                    continue
                if role == TRANSPOSE:
                    gs[i], _ = note(f"{role}.delete", _delete_body_counted(
                        gs[i], rbs, rbd, **kw))
                elif role == SYMMETRIC:
                    found = note(f"{role}.reverse", _query_body(
                        gs[fidx], rbs, rbd, **kwq))
                    gone = ~or_across_shards(
                        _scatter_back(found, rorig, n_del))
                    # the symmetric delete RIDES the two exchanges above:
                    # ``gone`` is replicated after the psum, the forward
                    # half of the (2B,) vmap candidate batch is owned by
                    # owner(src) (already delivered by the forward
                    # exchange, in global batch order) and the reverse
                    # half by owner(dst) (the transpose exchange) — so
                    # masking the received buckets per position
                    # reconstructs the vmap symmetric bucket exactly,
                    # with zero extra routing or collectives.
                    keep_f = (orig >= 0) & gone[jnp.clip(orig, 0)]
                    keep_r = (rorig >= 0) & gone[jnp.clip(rorig, 0)]
                    s2 = jnp.where(keep_f, bs, INVALID_VERTEX)
                    d2 = jnp.where(keep_f, bd, INVALID_VERTEX)
                    s2r = jnp.where(keep_r, rbs, INVALID_VERTEX)
                    d2r = jnp.where(keep_r, rbd, INVALID_VERTEX)
                    cs, cd, _ = compact(sym_del,
                                        jnp.concatenate([s2, s2r]),
                                        jnp.concatenate([d2, d2r]))
                    gs[i], _ = note(f"{role}.delete", _delete_body_counted(
                        gs[i], cs, cd, **kw))

        if il is not None:
            is_l, id_l, iw_l = il
            n_ins = is_l.shape[0] * n_shards
            bs, bd, bw, orig, _ = route(is_l, id_l, iw_l, fwd_ins)
            gs[fidx], m = note(f"{FORWARD}.insert", _insert_body_counted(
                gs[fidx], bs, bd, bw, **kw))
            ins_part = _scatter_back(m, orig, n_ins)
            if need_rev:
                tbs, tbd, tbw, _, _ = route(id_l, is_l, iw_l, tr_ins)
            for i, role in enumerate(roles):
                if i == fidx:
                    continue
                if role == TRANSPOSE:
                    gs[i], _ = note(f"{role}.insert", _insert_body_counted(
                        gs[i], tbs, tbd, tbw, **kw))
                elif role == SYMMETRIC:
                    # both directions already delivered: forward bucket
                    # owns the (s, d) half, transpose bucket the (d, s)
                    # half — their concat IS the vmap symmetric bucket
                    w2 = (None if bw is None
                          else jnp.concatenate([bw, tbw]))
                    cs, cd, cw = compact(sym_ins,
                                         jnp.concatenate([bs, tbs]),
                                         jnp.concatenate([bd, tbd]), w2)
                    gs[i], _ = note(f"{role}.insert", _insert_body_counted(
                        gs[i], cs, cd, cw, **kw))

        # epoch close folded into the single program (same as the vmap body)
        gs = [update_slab_pointers(g) for g in gs]
        return (tuple(jax.tree.map(lambda x: x[None], g) for g in gs),
                None if del_part is None else del_part[None],
                None if ins_part is None else ins_part[None], counts)

    vec = P(SHARD_AXIS)
    gspecs = tuple(graph_pspecs(g) for g in views)

    def batch_specs(t):
        return jax.tree.map(lambda _: vec, t)

    out_views, del_parts, ins_parts, counts = jax.shard_map(
        _body, mesh=mesh,
        in_specs=(gspecs, batch_specs(dels), batch_specs(ins)),
        out_specs=(gspecs,
                   None if dels is None else P(SHARD_AXIS, None),
                   None if ins is None else P(SHARD_AXIS, None), vec),
        check_vma=False)(views, dels, ins)
    # each batch position is owned by exactly one shard: OR the partials
    ins_mask = None if ins_parts is None else ins_parts.any(axis=0)
    del_mask = None if del_parts is None else del_parts.any(axis=0)
    probes = {k: _across_shards(ProbeCount(hops=h, visits=v, width=widths[k],
                                           rows=r, packs=p), n_shards)
              for k, (h, v, r, p) in counts.items()}
    return out_views, ins_mask, del_mask, probes


_APPLY_SM_STATIC = _APPLY_STATIC + ("mesh",)
_apply_sm_don = jax.jit(_sharded_apply_sm, static_argnames=_APPLY_SM_STATIC,
                        donate_argnums=(0,))


# ----------------------------------------------------------------------------
# the store
# ----------------------------------------------------------------------------

class ShardedGraphStore(VersionedStoreBase):
    """Forward + transposed + symmetric ShardedSlabGraph views as one
    versioned unit (the sharded ``GraphStore`` — the shared
    ``VersionedStoreBase`` listener/log/version protocol, so
    ``PropertyRegistry`` and ``RequestPipeline`` apply)."""

    def __init__(self, views: Dict[str, ShardedSlabGraph], *, weighted: bool,
                 version: int = 0, log_capacity: int = 64,
                 maintenance=None, dispatch: str = "auto"):
        assert FORWARD in views, "a store always carries the forward view"
        unknown = set(views) - set(ALL_VIEWS)
        assert not unknown, f"unknown views {unknown}"
        assert dispatch in ("auto", "vmap", "shard_map"), dispatch
        super().__init__(version=version, log_capacity=log_capacity,
                         maintenance=maintenance)
        self._views = dict(views)
        self.weighted = bool(weighted)
        # "vmap" | "shard_map" | "auto" (shard_map iff pools are mesh-placed)
        self.dispatch = dispatch
        # host-exact accounting (satellites of the single-program plane):
        #   _high_water[name] — upper bound on the worst shard's next_free,
        #     bumped by per-epoch routed-insert counts so steady-state
        #     epochs never block on a device read (primed lazily / after
        #     maintenance by one sync);
        #   _sticky_caps[(mode, slot)] — routing caps that only ratchet up,
        #     so a drifting batch mix stops walking pow2 rungs through new
        #     jit specialisations (reset at maintenance);
        #   recompile_count — distinct fused-epoch specialisations
        #     dispatched (what the bench logs).
        self._high_water: Dict[str, int] = {}
        self._sticky_caps: Dict[tuple, int] = {}
        self._dispatch_keys: set = set()
        self.recompile_count = 0

    # ------------------------------------------------------ mesh / dispatch
    def place_on_mesh(self, mesh: Mesh) -> "ShardedGraphStore":
        """Pin every view's stacked pools to the ("shard",) mesh; from then
        on ``dispatch="auto"`` runs epochs and analytics as single
        shard_map programs (DESIGN.md §9).  Returns self."""
        for name in list(self._views):
            self._views[name] = _place_graph(self._views[name], mesh)
        return self

    @property
    def mesh(self) -> Optional[Mesh]:
        return self.forward.mesh

    def _mode(self) -> str:
        if self.dispatch == "auto":
            return "shard_map" if self.mesh is not None else "vmap"
        if self.dispatch == "shard_map" and self.mesh is None:
            raise ValueError("dispatch='shard_map' needs mesh-placed views "
                             "— call store.place_on_mesh(mesh) first")
        return self.dispatch

    # ------------------------------------------------- host-exact accounting
    def _high(self, name: str) -> int:
        """Host upper bound on the view's worst-shard ``next_free`` (one
        device sync to prime; exact insert accounting afterwards)."""
        if name not in self._high_water:
            self._high_water[name] = int(
                jnp.max(self._views[name].graphs.next_free))
        return self._high_water[name]

    def sweep_rows(self, view: str = FORWARD) -> int:
        """Static sweep row bound for the analytics (``rows=``): the
        allocated-prefix high-water mark, quantized up to the sweep block
        size so jit specialisations stay bounded while sweeps skip the
        pow2 capacity slack."""
        cap = int(self._views[view].graphs.keys.shape[1])
        return min(cap, -(-self._high(view) // 256) * 256)

    def _cap(self, mode: str, slot: str, need: int) -> int:
        """Sticky routing cap: ratchets up only (reset at maintenance)."""
        cap = max(self._sticky_caps.get((mode, slot), 1), need)
        self._sticky_caps[(mode, slot)] = cap
        return cap

    def _route_metrics(self, i_s, d_s, S: int) -> None:
        """Per-shard forward-route counts + imbalance gauge (metrics-on
        path only — one host bincount over the already-canonical batch;
        never touches device state, so pools stay telemetry-neutral)."""
        for kind, arr in (("ins", i_s), ("del", d_s)):
            if not len(arr):
                continue
            counts = np.bincount(
                np.asarray(arr, np.int64) % S, minlength=S)
            for k in range(S):
                obs.inc(f"store.route.{kind}.shard{k}", int(counts[k]))
            mean = counts.mean()
            if mean > 0:
                obs.set_gauge(f"store.route.{kind}.imbalance",
                              float(counts.max() / mean))

    # ------------------------------------------------------------- construct
    @classmethod
    def from_edges(cls, n_vertices: int, n_shards: int, src, dst, w=None, *,
                   with_transpose: bool = True, with_symmetric: bool = True,
                   slack_slabs: int = 0,
                   log_capacity: int = 64,
                   maintenance=None,
                   dispatch: str = "auto",
                   mesh: Optional[Mesh] = None) -> "ShardedGraphStore":
        """Bulk-build every view host-side (``shard_from_edges_host`` —
        dense pools, dedup shared; the engine path serves the epochs).
        With ``mesh`` each view's pools go straight to their shards'
        devices, as after ``place_on_mesh``."""
        src, dst, w = dedup_pairs(src, dst, w)
        kw = dict(slack_slabs=slack_slabs, mesh=mesh)
        views = {FORWARD: shard_from_edges_host(
            n_vertices, n_shards, src, dst, w, **kw)}
        if with_transpose:
            views[TRANSPOSE] = shard_from_edges_host(
                n_vertices, n_shards, dst, src, w, **kw)
        if with_symmetric:
            s2 = np.concatenate([src, dst])
            d2 = np.concatenate([dst, src])
            w2 = None if w is None else np.concatenate([w, w])
            views[SYMMETRIC] = shard_from_edges_host(
                n_vertices, n_shards, s2, d2, w2, **kw)
        return cls(views, weighted=w is not None, log_capacity=log_capacity,
                   maintenance=maintenance, dispatch=dispatch)

    # ------------------------------------------------------------- accessors
    @property
    def forward(self) -> ShardedSlabGraph:
        return self._views[FORWARD]

    @property
    def transpose(self) -> Optional[ShardedSlabGraph]:
        return self._views.get(TRANSPOSE)

    @property
    def symmetric(self) -> Optional[ShardedSlabGraph]:
        return self._views.get(SYMMETRIC)

    @property
    def views(self) -> Dict[str, ShardedSlabGraph]:
        return dict(self._views)

    @property
    def n_shards(self) -> int:
        return self.forward.n_shards

    @property
    def n_vertices(self) -> int:
        return self.forward.n_vertices_global

    @property
    def n_edges(self) -> int:
        return int(jnp.sum(self.forward.graphs.n_edges))

    @property
    def out_degree(self) -> jnp.ndarray:
        """GLOBAL out-degrees, reassembled from the forward shards."""
        return reassemble_global(self.forward.graphs.degree, self.n_vertices)

    @property
    def in_degree(self) -> jnp.ndarray:
        if self.transpose is None:
            raise ValueError("in-degrees live on the transpose view; build "
                             "the store with with_transpose=True")
        return reassemble_global(self.transpose.graphs.degree,
                                 self.n_vertices)

    # ----------------------------------------------------------------- apply
    def apply(self, ins_src=None, ins_dst=None, ins_w=None,
              del_src=None, del_dst=None) -> AppliedBatch:
        """Apply one mixed update batch to every view; close the epoch.

        One host dedup, host-exact routing-cap sizing (sticky — no overflow
        by construction, no per-batch pow2 walking), ONE donated multi-view
        dispatch: a single shard_map program when the views are mesh-placed
        (``place_on_mesh``), the stacked-vmap fallback otherwise.  Pool
        results are leaf-for-leaf identical between the two.  Capacity
        checks run on host high-water accounting — no per-epoch device
        sync — see module doc.
        """
        # admission guard FIRST, on the raw inputs (see GraphStore.apply)
        validate_batch(ins_src, ins_dst, ins_w, del_src, del_dst,
                       n_vertices=self.n_vertices)
        t0 = time.perf_counter()
        epoch_span = obs.span("store.apply", version=self.version,
                              sharded=True)
        epoch_span.__enter__()
        try:
            batch = self._apply_inner(t0, epoch_span, ins_src, ins_dst,
                                      ins_w, del_src, del_dst)
        except BaseException as e:
            # the black box: dump a post-mortem bundle beside the WAL at
            # the moment of death (never raises, skips recoverable kinds)
            self._dump_postmortem(e)
            raise
        finally:
            epoch_span.__exit__(None, None, None)

        # -- maintenance + audit planes: policy checks on the closed epoch --
        self._auto_maintain()
        self._auto_audit()
        return batch

    def _apply_inner(self, t0, epoch_span, ins_src, ins_dst, ins_w,
                     del_src, del_dst) -> AppliedBatch:
        with obs.span("store.apply.host_dedup"):
            i_s, i_d, i_w, d_s, d_d = canonical_batch(
                ins_src, ins_dst, ins_w, del_src, del_dst,
                weighted=self.weighted)
        faults.fault_point("apply.admitted", version=self.version)
        _flight.record(_FL_ADMIT, self.version, len(i_s), len(d_s))
        roles = tuple(v for v in ALL_VIEWS if v in self._views)
        S = self.n_shards
        mode = self._mode()
        if obs.metrics.enabled():
            # per-shard route counts + imbalance (owner = vertex % S): the
            # forward view routes inserts by owner(src), deletes likewise
            self._route_metrics(i_s, d_s, S)

        def padded(n):
            # pow2 batch rungs, kept a multiple of S so the shard_map path
            # can block-partition the batch (identical padding in both
            # modes keeps dispatch-mode identity trivially checkable)
            p = _pow2(n)
            return -(-p // S) * S

        p_del = padded(len(d_s)) if len(d_s) else 0
        p_ins = padded(len(i_s)) if len(i_s) else 0

        # -- host-exact per-view bucket sizing + capacity -------------------
        # shard_map buckets are per-(source block, owner) pairs (~1/S the
        # vmap per-owner counts); both modes share the sticky ratchet.
        def cap_of(slot, arr, block=None):
            # total cap (= the vmap bucket width): rung of the max per-owner
            # count; shard_map additionally carries the per-(source block,
            # owner) PAIR cap its all-to-all buckets route through before
            # compacting back down to the total-cap layout.  Symmetric slots
            # pass block=None — their candidates never route in shard_map
            # mode (they ride the forward + transpose exchanges), the total
            # is only the compaction width.
            tot = (1 if not len(arr) else
                   self._cap(mode, slot, _cap_rung(max_owner_count(arr, S))))
            if mode != "shard_map" or block is None:
                return tot
            pair = (1 if not len(arr) else
                    self._cap(mode, slot + "_pair",
                              routing_cap_blocks(arr, S, block)))
            return (pair, tot)

        with obs.span("store.apply.route", mode=mode):
            one = (1, 1) if mode == "shard_map" else 1
            fwd_ins = tr_ins = fwd_del = tr_del = one
            sym_ins = sym_del = 1
            if len(d_s):
                fwd_del = cap_of("fwd_del", d_s, p_del // S)
                tr_del = cap_of("tr_del", d_d, p_del // S)
                sym_del = cap_of("sym_del", _sym_concat_u32(d_s, d_d, p_del))
            if len(i_s):
                fwd_ins = cap_of("fwd_ins", i_s, p_ins // S)
                tr_ins = cap_of("tr_ins", i_d, p_ins // S)
                sym_ins = cap_of("sym_ins", _sym_concat_u32(i_s, i_d, p_ins))
                per_view = {
                    FORWARD: max_owner_count(i_s, S),
                    TRANSPOSE: max_owner_count(i_d, S),
                    SYMMETRIC: max_owner_count(np.concatenate([i_s, i_d]),
                                               S)}

                def _ensure(name):
                    reserve = next_pow2(per_view[name], lo=1) + 64
                    sg = self._views[name]
                    cap_before = int(sg.graphs.keys.shape[1])
                    if cap_before - self._high(name) < reserve:
                        # the running estimate charges a whole slab per
                        # routed insert, so it overestimates hard; before
                        # paying a pool concat, re-prime with one exact
                        # device read (a sync only when the estimate
                        # crosses capacity — not per epoch) so the bound
                        # cannot compound into spurious per-epoch growth
                        faults.fault_point("store.capacity_grow",
                                           view=name, version=self.version)
                        self._high_water[name] = int(
                            jnp.max(sg.graphs.next_free))
                        self._views[name] = ensure_capacity_sharded(
                            sg, reserve, high=self._high_water[name])
                        cap_after = int(
                            self._views[name].graphs.keys.shape[1])
                        if cap_after != cap_before:
                            obs.instant("capacity_grow", view=name,
                                        before=cap_before, after=cap_after)
                            obs.emit_event("capacity_grow", view=name,
                                           version=self.version,
                                           before=cap_before,
                                           after=cap_after)
                            obs.inc("store.capacity_grow")
                            _flight.record(_FL_GROW, self.version,
                                           cap_after)
                    self._last_reserve[name] = reserve

                for name in roles:
                    run_with_retries(partial(_ensure, name),
                                     budget=self.retry,
                                     site="store.capacity_grow")
            caps = (fwd_del, tr_del, sym_del, fwd_ins, tr_ins, sym_ins)

        # -- canonical device batches (every view derives from these) -------
        del_sj = del_dj = del_mask = None
        ins_sj = ins_dj = ins_wj = ins_mask = None
        dels = ins = None
        if len(d_s):
            del_sj, del_dj = _pad_u32(d_s, p_del), _pad_u32(d_d, p_del)
            dels = (del_sj, del_dj)
        if len(i_s):
            ins_sj, ins_dj = _pad_u32(i_s, p_ins), _pad_u32(i_d, p_ins)
            ins_wj = _pad_f32(i_w, p_ins)
            ins = (ins_sj, ins_dj, ins_wj)

        # -- durability: journal the canonical batch, THEN dispatch ---------
        wal_token = self._wal_append(i_s, i_d, i_w, d_s, d_d)
        faults.fault_point("apply.post_wal", version=self.version)
        _flight.record(_FL_POST_WAL, self.version,
                       0 if wal_token is None else 1)

        try:
            # -- single donated route+mutate dispatch over every live view --
            n_inserted = n_deleted = 0
            if ins is not None or dels is not None:
                key = (mode, roles, caps, p_del, p_ins, i_w is not None)
                if key not in self._dispatch_keys:
                    self._dispatch_keys.add(key)
                    self.recompile_count += 1
                    obs.inc("store.sharded.recompiles")
                    obs.instant("sharded_recompile", mode=mode)
                with obs.span("store.apply.dispatch", mode=mode,
                              version=self.version,
                              views=len(roles)) as sp:
                    if mode == "shard_map":
                        in_views = _copy_aliased(
                            tuple(self._views[r].graphs for r in roles))
                        new_graphs, ins_mask, del_mask, probes = \
                            _apply_sm_don(in_views, dels, ins, roles=roles,
                                          n_shards=S, caps=caps,
                                          mesh=self.mesh)
                        for r, g in zip(roles, new_graphs):
                            self._views[r] = dataclasses.replace(
                                self._views[r], graphs=g)
                    else:
                        in_views = _copy_aliased(
                            tuple(self._views[r] for r in roles))
                        new_views, ins_mask, del_mask, probes = \
                            _apply_jit_don(in_views, ins, dels, roles=roles,
                                           n_shards=S, caps=caps)
                        for r, g in zip(roles, new_views):
                            self._views[r] = g
                    count_probes(probes)
                    # the applied counts and the probe counters: one
                    # transfer
                    n_deleted, n_inserted = (int(n) for n in sp.fetch(
                        tuple(0 if m is None else
                              jnp.sum(m.astype(jnp.int32))
                              for m in (del_mask, ins_mask))))
                # exact host accounting: the worst shard allocates at most
                # its routed insert count in new slabs this epoch
                if len(i_s):
                    for name in roles:
                        self._high_water[name] = (self._high(name)
                                                  + per_view[name])
            faults.fault_point("apply.pre_close", version=self.version)
            _flight.record(_FL_DISPATCH, self.version,
                           n_inserted, n_deleted)

            # -- version bump + notification (epoch still open) -------------
            with obs.span("store.apply.notify"):
                batch = self._record_batch(
                    ins_src=ins_sj, ins_dst=ins_dj, ins_w=ins_wj,
                    ins_mask=ins_mask, del_src=del_sj, del_dst=del_dj,
                    del_mask=del_mask,
                    n_inserted=n_inserted, n_deleted=n_deleted)

            # -- close the epoch: folded into the fused dispatch above; only
            # an empty batch (no dispatch) still closes here, where it is a
            # no-op value-wise (pointers already sit at the previous close)
            if ins is None and dels is None:
                with obs.span("store.apply.epoch_close"):
                    for name, sg in self._views.items():
                        self._views[name] = dataclasses.replace(
                            sg, graphs=update_slab_pointers(sg.graphs))
            faults.fault_point("apply.post_close", version=self.version)
            _flight.record(_FL_CLOSE, batch.version,
                           n_inserted, n_deleted)
        except faults.InjectedCrash:
            raise              # a simulated kill: the WAL record survives
        except BaseException:
            # failed apply: drop the journaled batch (see GraphStore.apply)
            if wal_token is not None:
                self.wal.rollback(wal_token)
            raise

        epoch_span.annotate(inserted=n_inserted, deleted=n_deleted)
        if obs.metrics.enabled():
            obs.observe("store.apply", time.perf_counter() - t0)
            obs.inc("store.apply.epochs")
            obs.inc("store.apply.inserted", n_inserted)
            obs.inc("store.apply.deleted", n_deleted)
        return batch

    # ----------------------------------------------------- maintenance plane
    def pool_stats(self, view: str = FORWARD) -> dict:
        """Aggregated pool health across the view's shards (per-shard
        ``core.pool_stats`` summed / maxed so policy thresholds read the
        same way as on the unsharded store; capacity is PER SHARD — the
        stacked pools are rectangular)."""
        from ..core.slab_graph import pool_stats as _pool_stats
        sg = self._views[view]
        per = [_pool_stats(shard_slice(sg, k)) for k in range(self.n_shards)]
        live = sum(p["live_lanes"] for p in per)
        tomb = sum(p["tombstone_lanes"] for p in per)
        alloc = sum(p["allocated_slabs"] for p in per)
        mean_chain = float(np.mean([p["mean_chain"] for p in per]))
        return {
            "capacity_slabs": per[0]["capacity_slabs"],
            "next_free": max(p["next_free"] for p in per),
            "free_top": min(p["free_top"] for p in per),
            "free_slabs": min(p["free_slabs"] for p in per),
            "allocated_slabs": alloc,
            "dead_slabs": sum(p["dead_slabs"] for p in per),
            "live_lanes": live,
            "tombstone_lanes": tomb,
            "tombstone_ratio": tomb / max(1, live + tomb),
            "occupancy": live / max(1, alloc * SLAB_WIDTH),
            "max_chain": max(p["max_chain"] for p in per),
            "mean_chain": mean_chain,
            "pool_bytes": sum(p["pool_bytes"] for p in per),
            "n_edges": sum(p["n_edges"] for p in per),
            "per_shard": per,
        }

    def _compact_view(self, sg: ShardedSlabGraph, policy, *, shrink: bool,
                      slack_slabs: int):
        from ..kernels.slab_compact import compact_shards
        graphs, rep = compact_shards(sg.graphs, impl=policy.impl,
                                     shrink=shrink, slack_slabs=slack_slabs)
        return dataclasses.replace(sg, graphs=graphs), rep

    def _reclaim_view(self, sg: ShardedSlabGraph):
        from ..kernels.slab_compact import reclaim_shards
        graphs, n = reclaim_shards(sg.graphs)
        return dataclasses.replace(sg, graphs=graphs), n

    def _maintain_views(self, action: str, policy, *, shrink: bool):
        out = super()._maintain_views(action, policy, shrink=shrink)
        # compaction/reclamation relocates slabs (and may shrink pools):
        # the host high-water bounds and sticky routing caps are stale —
        # drop them so the next epoch re-primes (one sync) and cap rungs
        # can shrink back to the live workload
        self._high_water.clear()
        self._sticky_caps.clear()
        if self.mesh is not None:
            # maintenance kernels run outside the shard_map program; pin
            # their outputs back onto the mesh explicitly
            self.place_on_mesh(self.mesh)
        return out

    # --------------------------------------------------------------- queries
    def query(self, src, dst) -> np.ndarray:
        """Batched edge-membership against the sharded forward view (host
        arrays in, host bool array out, trimmed to the query length)."""
        from ..distributed.sharded_graph import query_edges_sharded
        src = np.asarray(src, np.uint32)
        dst = np.asarray(dst, np.uint32)
        p = _pow2(max(len(src), 1))
        cap = routing_cap(src, self.n_shards)
        found = query_edges_sharded(self.forward, _pad_u32(src, p),
                                    _pad_u32(dst, p), cap=cap)
        return np.asarray(found)[:len(src)]

    def neighbors(self, vertices, *, out_capacity: int = 4096
                  ) -> EdgeFrontier:
        """Current out-edges of ``vertices`` as one EdgeFrontier: per-owner
        chain walks on the local shards, src ids re-globalised and merged
        (host-facing query API — RequestPipeline's NeighborsQuery)."""
        vertices = np.asarray(vertices, np.uint32)
        S = self.n_shards
        cap = _pow2(out_capacity)
        srcs, dsts, ws = [], [], []
        overflow = False
        for k in range(S):
            m = (vertices % np.uint32(S)) == k
            if not m.any():
                continue
            g = shard_slice(self.forward, k)
            loc = (vertices[m] // np.uint32(S)).astype(np.uint32)
            p = _pow2(max(len(loc), 1))
            vmask = jnp.asarray(np.arange(p) < len(loc))
            ef = expand_vertices(g, _pad_u32(loc, p), vmask,
                                 out_capacity=cap, max_bpv=1)
            n = int(ef.size)
            overflow = overflow or bool(ef.overflow)
            srcs.append(np.asarray(ef.src)[:n].astype(np.int64) * S + k)
            dsts.append(np.asarray(ef.dst)[:n])
            ws.append(np.asarray(ef.weight)[:n])
        src = np.concatenate(srcs) if srcs else np.zeros(0, np.int64)
        n = min(len(src), cap)
        overflow = overflow or len(src) > cap
        out_src = np.zeros(cap, np.uint32)
        out_dst = np.zeros(cap, np.uint32)
        out_w = np.zeros(cap, np.float32)
        out_src[:n] = src[:n].astype(np.uint32)
        if srcs:
            out_dst[:n] = np.concatenate(dsts)[:n]
            out_w[:n] = np.concatenate(ws)[:n]
        return EdgeFrontier(jnp.asarray(out_src), jnp.asarray(out_dst),
                            jnp.asarray(out_w), jnp.asarray(n, jnp.int32),
                            jnp.asarray(overflow))

    # ------------------------------------------------------------ checkpoint
    def _resilience_meta(self) -> dict:
        # the sharded store's host accounting (high-water capacity bounds,
        # sticky routing caps) steers capacity growth and jit
        # specialisation — persist it so a WAL replay after restore makes
        # the same growth decisions as the crashed process (leaf-for-leaf
        # recovery, including pool SHAPES)
        meta = super()._resilience_meta()
        meta["high_water"] = {k: int(v)
                              for k, v in self._high_water.items()}
        meta["sticky_caps"] = [[m, s, int(c)]
                               for (m, s), c in self._sticky_caps.items()]
        return meta

    def _adopt_resilience_meta(self, meta: dict) -> None:
        super()._adopt_resilience_meta(meta)
        res = meta.get("resilience") or {}
        self._high_water = {k: int(v)
                            for k, v in res.get("high_water", {}).items()}
        self._sticky_caps = {(m, s): int(c)
                             for m, s, c in res.get("sticky_caps", [])}

    def save(self, ckpt_dir, step: Optional[int] = None, *, registry=None,
             extra: Optional[dict] = None, keep_last: int = 3):
        """Persist every view's stacked pools (+ property states)
        atomically — the sharded rendering of ``GraphStore.save``.  The
        checkpoint is mesh-agnostic: ``restore`` rebuilds with
        ``mesh=None`` and ``place_on_mesh`` re-pins on whatever mesh the
        new job brings up (elastic restart)."""
        from ..checkpoint import ckpt
        step = self.version if step is None else int(step)
        props = {} if registry is None else registry.states()
        prop_versions = {} if registry is None else registry.versions()
        meta = {
            "stream_store": True,
            "sharded_store": True,
            "version": int(self.version),
            "n_vertices": int(self.n_vertices),
            "n_shards": int(self.n_shards),
            "weighted": bool(self.weighted),
            "views": {name: int(sg.graphs.n_buckets)
                      for name, sg in self._views.items()},
            "prop_versions": {k: int(v) for k, v in prop_versions.items()},
            "resilience": self._resilience_meta(),
        }
        if extra:
            meta.update(extra)
        path = ckpt.save(
            ckpt_dir, step,
            {"views": {name: sg.graphs
                       for name, sg in self._views.items()},
             "props": props},
            extra=meta, keep_last=keep_last)
        if self.wal is not None and step == self.version:
            self.wal.truncate(self.version)
        return path

    @classmethod
    def restore(cls, ckpt_dir, *, step: Optional[int] = None,
                specs: Sequence = (), policies: Optional[Dict[str, str]] = None,
                log_capacity: int = 64, maintenance=None,
                dispatch: str = "auto"):
        """Rebuild (store, registry) from a sharded checkpoint (the
        ``GraphStore.restore`` contract; views come back with
        ``mesh=None`` — call ``place_on_mesh`` to re-pin)."""
        import jax as _jax

        from ..checkpoint import ckpt
        from ..checkpoint.ckpt import CheckpointError
        from ..core.slab_graph import empty as _empty
        manifest = ckpt.read_manifest(ckpt_dir, step=step)
        meta = manifest["extra"]
        missing = [k for k in ("n_vertices", "n_shards", "weighted",
                               "views", "prop_versions")
                   if k not in meta]
        if missing or not meta.get("sharded_store"):
            raise CheckpointError(
                f"{ckpt_dir} step {manifest['step']} is not a "
                f"ShardedGraphStore checkpoint (missing meta: "
                f"{missing or ['sharded_store']}) — pick another step= "
                "or re-checkpoint")
        V = int(meta["n_vertices"])
        S = int(meta["n_shards"])
        weighted = bool(meta["weighted"])
        n_local = -(-V // S)

        def view_like(n_buckets: int) -> ShardedSlabGraph:
            # structural skeleton only: the loader takes shapes from the
            # saved arrays and dtypes/treedef from this — the static
            # n_buckets/n_vertices meta must match the saved pools, the
            # leaf shapes need not
            bc = np.zeros(n_local, np.int32)
            bc[0] = n_buckets
            g0 = _empty(n_local, bc, n_buckets + 1, weighted=weighted)
            return _jax.tree.map(lambda x: x[None], g0)

        like_views = {name: view_like(nb)
                      for name, nb in meta["views"].items()}
        spec_by_name = {s.name: s for s in specs}
        like_props = {}
        for name in meta["prop_versions"]:
            if name not in spec_by_name:
                raise KeyError(
                    f"checkpoint stores property {name!r}; pass its "
                    f"PropertySpec via specs= to restore it")
            like_props[name] = spec_by_name[name].state_like(V)
        tree, _ = ckpt.restore(ckpt_dir, {"views": like_views,
                                          "props": like_props},
                               step=manifest["step"])
        views = {name: ShardedSlabGraph(graphs=graphs, n_shards=S,
                                        n_vertices_global=V)
                 for name, graphs in tree["views"].items()}
        store = cls(views, weighted=weighted, version=meta["version"],
                    log_capacity=log_capacity, maintenance=maintenance,
                    dispatch=dispatch)
        store._adopt_resilience_meta(meta)

        registry = None
        if spec_by_name:
            from .properties import PropertyRegistry
            registry = PropertyRegistry(store)
            policies = policies or {}
            for name, spec in spec_by_name.items():
                if name in tree["props"]:
                    registry.register(spec,
                                      policy=policies.get(name, "lazy"),
                                      _state=tree["props"][name],
                                      _version=meta["prop_versions"][name])
                else:
                    registry.register(spec, policy=policies.get(name, "lazy"))
        return store, registry


# ----------------------------------------------------------------------------
# sharded stream_property hooks (registered via PropertyRegistry)
# ----------------------------------------------------------------------------

def sharded_pagerank_property(*, damping: float = 0.85,
                              error_margin: float = 1e-5,
                              max_iter: int = 100):
    """PropertySpec: PageRank over the sharded transpose (in-edge) view with
    the global out-degree vector; warm start — incremental == decremental ==
    batch-independent, so lazy replay collapses to one solve."""
    from .properties import PropertySpec

    def _run(store, init_pr=None):
        if store.transpose is None:
            raise ValueError("sharded pagerank sweeps the transpose view; "
                             "build the store with with_transpose=True")
        pr, _ = pagerank_sharded(store.transpose, store.out_degree,
                                 init_pr=init_pr, damping=damping,
                                 error_margin=error_margin,
                                 max_iter=max_iter,
                                 rows=store.sweep_rows(TRANSPOSE))
        return pr

    return PropertySpec(
        name="pagerank",
        init=lambda store: _run(store),
        on_batch=lambda store, state, batch: _run(store, init_pr=state),
        refresh=lambda store: _run(store),
        state_like=lambda n: jnp.zeros((n,), jnp.float32),
        collapse_replay=True)


def sharded_wcc_property(*, max_iters: int = 100000):
    """PropertySpec: min-id component labels via sharded min-label sweeps
    over the symmetric union.  Insert-only epochs warm start from the
    current labels (labels only decrease under inserts); epochs that delete
    fall back to the static recompute (decremental WCC stays open, §6.4)."""
    from .properties import PropertySpec

    def _run(store, init_labels=None):
        if store.symmetric is None:
            raise ValueError("sharded wcc sweeps the symmetric view; build "
                             "the store with with_symmetric=True")
        labels, _ = wcc_sharded(store.symmetric, init_labels=init_labels,
                                max_iters=max_iters,
                                rows=store.sweep_rows(SYMMETRIC))
        return labels

    def _on_batch(store, labels, batch):
        if batch.n_deleted > 0:
            return _run(store)
        return _run(store, init_labels=labels)

    # a deleting epoch's catch-up IS a refresh: replay one epoch at most
    return PropertySpec(
        name="wcc", init=_run, on_batch=_on_batch, refresh=_run,
        max_replay=1, state_like=lambda n: jnp.zeros((n,), jnp.int32))


def sharded_bfs_property(src: int, *, max_iters: int = 100000):
    """PropertySpec: BFS level distances from ``src`` via sharded unit
    min-plus sweeps over the transpose (in-edge) view.  Insert-only epochs
    warm start from the current distances (valid upper bounds); deleting
    epochs recompute.  Requires an UNWEIGHTED store (levels, not SSSP)."""
    from .properties import PropertySpec

    def _run(store, init_dist=None):
        assert not store.weighted, \
            "sharded_bfs_property needs an unweighted store"
        if store.transpose is None:
            raise ValueError("sharded bfs sweeps the transpose view; build "
                             "the store with with_transpose=True")
        dist, _ = bfs_sharded(store.transpose, src=src, init_dist=init_dist,
                              max_iters=max_iters,
                              rows=store.sweep_rows(TRANSPOSE))
        return dist

    def _on_batch(store, dist, batch):
        if batch.n_deleted > 0:
            return _run(store)
        return _run(store, init_dist=dist)

    # a deleting epoch's catch-up IS a refresh: replay one epoch at most
    return PropertySpec(
        name=f"bfs_{src}", init=_run, on_batch=_on_batch, refresh=_run,
        max_replay=1,
        state_like=lambda n: jnp.zeros((n,), jnp.int32))


def sharded_triangle_property(*, impl: str = "auto"):
    """PropertySpec: live global triangle count over the sharded SYMMETRIC
    view — per-shard intersect counts (``triangles_sharded``'s rotated
    all-to-all decomposition) folded by one collective reduction.

    Epochs that change the edge set recount; maintenance and no-op epochs
    keep the scalar as-is (compaction perms cannot invalidate it).  The
    count is a pure function of the current graph, so lazy replay collapses
    to a single recount.  Bit-identical to ``triangles_static`` /
    ``triangle_stream_property`` on the unsharded union.
    """
    from .properties import PropertySpec

    def _run(store):
        if store.symmetric is None:
            raise ValueError("sharded triangle counting probes the "
                             "symmetric view; build the store with "
                             "with_symmetric=True")
        return triangles_sharded(store.symmetric, impl=impl)

    def _on_batch(store, count, batch):
        if batch.maintenance or (batch.n_inserted == 0
                                 and batch.n_deleted == 0):
            return count
        return _run(store)

    return PropertySpec(
        name="triangles", init=_run, on_batch=_on_batch, refresh=_run,
        state_like=lambda n: jnp.zeros((), jnp.int32),
        collapse_replay=True)

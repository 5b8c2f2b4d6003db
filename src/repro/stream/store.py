"""GraphStore — the versioned multi-view update plane of `repro.stream`.

Meerkat's evaluation loop (apply a batch of edge inserts/deletes, then
incrementally recompute analytics) is the inner loop of a streaming-graph
service.  The store owns that loop end-to-end: it holds the forward,
transposed, and symmetric `SlabGraph` views as ONE versioned unit and applies
every update batch to all of them consistently, so algorithm code can always
pick the view its sweep direction wants (DESIGN.md §3) without ever seeing a
half-updated pair of views.

Contract per ``apply(inserts, deletes)`` (DESIGN.md §5/§6):

  1. the batch is canonicalised ONCE on the host (``canonical_batch``:
     dedup both halves, pad to a power-of-two lane count) — the transpose
     and symmetric batches are *derived* from that one canonical batch on
     device (swap / concat), never re-deduped or re-hashed per view,
  2. ``ensure_capacity`` runs automatically on every live view (growth is
     power-of-two quantized, so repeated growth walks a small ladder of
     pool shapes),
  3. deletions apply before insertions (a pair present in both ends the epoch
     *present*),
  4. the symmetric view is maintained as the true union of both directions:
     deleting (s,d) removes (s,d)/(d,s) from it only when the reverse edge
     (d,s) is itself absent from the post-delete forward view,
  5. out-degrees stay on device (``store.out_degree`` IS the forward view's
     ``degree`` field — no host shadow),
  6. registered listeners (the property registry) are notified while the
     update epoch is still OPEN, then every view's epoch is closed via
     ``update_slab_pointers`` and the monotonic ``version`` has been bumped,
  7. with a ``MaintenancePolicy`` attached, the closed epoch is inspected
     (``pool_stats``) and — on a trigger — every view compacts or reclaims
     as one versioned unit (DESIGN.md §8): a ``maintenance=True`` batch
     bumps the version and notifies listeners, vertex-keyed property
     states survive, retained slab handles are invalidated via the
     compaction permutation.

All live views mutate through ONE ``update_views`` dispatch (the stacked
slab-update engine invocation, DESIGN.md §6) with their buffers donated —
the pools update in place.  Consequence: a ``SlabGraph`` obtained from
``store.forward``/``.transpose``/``.symmetric`` is only valid until the
next ``apply``; re-read the property after each epoch (move semantics,
like the GPU original's in-place slab writes).

A bounded log of applied batches supports lazy property catch-up
(``batches_since``); when the log has been truncated the registry falls back
to a static refresh.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs import flight as _flight
from ..core.batch import query_edges, update_views
from ..core.hashing import INVALID_VERTEX
from ..core.slab_graph import (SlabGraph, empty, ensure_capacity,
                               from_edges_host, next_pow2,
                               update_slab_pointers)
from ..core.worklist import EdgeFrontier, expand_vertices
from ..resilience import faults
from ..resilience.guard import (RetryBudget, run_with_retries,
                                validate_batch)

FORWARD = "forward"
TRANSPOSE = "transpose"
SYMMETRIC = "symmetric"
ALL_VIEWS = (FORWARD, TRANSPOSE, SYMMETRIC)

# Flight-recorder codes (interned once at import): each apply phase writes
# one ring event even when tracing/metrics are off, so a post-mortem's
# last-N window shows exactly which phase an epoch last cleared.
_FL_ADMIT = _flight.intern("store.apply.admitted")
_FL_GROW = _flight.intern("store.capacity_grow")
_FL_POST_WAL = _flight.intern("store.apply.post_wal")
_FL_DISPATCH = _flight.intern("store.apply.dispatch")
_FL_CLOSE = _flight.intern("store.apply.close")
_FL_MAINTAIN = _flight.intern("store.maintain")


# Batch lane counts quantize through the same pow2 ladder as pool growth.
_pow2 = next_pow2


def _pad_u32(a: np.ndarray, n: int) -> jnp.ndarray:
    out = np.full(n, INVALID_VERTEX, np.uint32)
    out[:len(a)] = a
    return jnp.asarray(out)


def _pad_f32(a: Optional[np.ndarray], n: int) -> Optional[jnp.ndarray]:
    if a is None:
        return None
    out = np.zeros(n, np.float32)
    out[:len(a)] = a
    return jnp.asarray(out)


def dedup_pairs(src, dst, w=None) -> Tuple[np.ndarray, np.ndarray,
                                           Optional[np.ndarray]]:
    """Host-side (src,dst) dedup, first occurrence wins (insert semantics)."""
    src = np.asarray(src, dtype=np.uint32)
    dst = np.asarray(dst, dtype=np.uint32)
    w = None if w is None else np.asarray(w, dtype=np.float32)
    if len(src) == 0:
        return src, dst, w
    key = (src.astype(np.uint64) << np.uint64(32)) | dst.astype(np.uint64)
    _, idx = np.unique(key, return_index=True)
    idx.sort()
    return src[idx], dst[idx], None if w is None else w[idx]


def canonical_batch(ins_src, ins_dst, ins_w, del_src, del_dst, *,
                    weighted: bool):
    """THE one host-side canonicalisation per ``apply``: dedup the insert
    and delete halves (first occurrence wins) and default missing insert
    weights on weighted stores.  Every per-view batch is derived from this
    canonical batch on device — no view re-dedups."""
    i_s, i_d, i_w = dedup_pairs(
        () if ins_src is None else ins_src,
        () if ins_dst is None else ins_dst, ins_w)
    d_s, d_d, _ = dedup_pairs(
        () if del_src is None else del_src,
        () if del_dst is None else del_dst)
    if weighted and len(i_s) and i_w is None:
        i_w = np.ones(len(i_s), np.float32)
    return i_s, i_d, i_w, d_s, d_d


@dataclasses.dataclass(frozen=True)
class AppliedBatch:
    """One closed update epoch, as seen by incremental property maintainers.

    Arrays are the padded device batches the views were mutated with; the
    masks mark edges *actually* inserted into / deleted from the forward view
    (duplicates and misses excluded).  ``ins_src is None`` means the epoch had
    no insert phase (likewise deletes).
    """
    version: int
    ins_src: Optional[jnp.ndarray]
    ins_dst: Optional[jnp.ndarray]
    ins_w: Optional[jnp.ndarray]
    ins_mask: Optional[jnp.ndarray]
    del_src: Optional[jnp.ndarray]
    del_dst: Optional[jnp.ndarray]
    del_mask: Optional[jnp.ndarray]
    n_inserted: int
    n_deleted: int
    #: epoch was a maintenance pass (compaction / slab reclamation): the
    #: edge set is untouched, vertex-keyed property states stay valid, and
    #: replay skips it — only retained slab handles are invalidated.
    maintenance: bool = False


class VersionedStoreBase:
    """The version / bounded-log / listener protocol both stores speak.

    This is the contract ``PropertyRegistry``'s catch-up relies on
    (``version`` monotonic, ``batches_since`` None past the log floor,
    listeners notified while the epoch is still open) — shared so the
    unsharded ``GraphStore`` and the ``ShardedGraphStore`` cannot drift.
    """

    def __init__(self, *, version: int = 0, log_capacity: int = 64,
                 maintenance=None):
        self.version = int(version)
        self._log_capacity = int(log_capacity)
        self._log: List[AppliedBatch] = []
        self._log_floor = int(version)  # version the oldest logged batch follows
        self._listeners: List[Callable[[AppliedBatch], None]] = []
        #: Optional MaintenancePolicy — evaluated at every epoch close.
        self.maintenance = maintenance
        self.maintenance_count = 0
        self.last_maintenance = None
        self._epochs_since_maint = 0
        #: per-view worst-case slab reservation of the most recent insert
        #: epoch — compaction keeps this much headroom so a shrunk pool
        #: doesn't have to grow right back for the next same-sized batch
        #: (no shrink/grow flapping at a pow2 rung edge).
        self._last_reserve: Dict[str, int] = {}
        #: exact tombstone accounting so the per-epoch policy check stays
        #: O(1): every recorded delete mints exactly one tombstone lane,
        #: and only maintenance ever clears them.
        self._tombstone_base = 0       # tombstones at the last maintenance
        self._deletes_since_maint = 0
        #: structured per-pass event stream (DESIGN.md §10): one dict per
        #: maintenance pass — trigger, tombstone ratio, capacity movement,
        #: slabs reclaimed — bounded like the batch log.  Mirrored into
        #: ``obs.metrics`` events when telemetry is on.
        self.maintenance_events: List[dict] = []
        # ----------------------------------------------- resilience plane
        #: optional WriteAheadLog — every apply journals its canonical
        #: batch (fsync) BEFORE the donated dispatch (DESIGN.md §11)
        self.wal = None
        #: optional AuditPolicy — pool invariant audits every N epochs
        self.audits = None
        self._epochs_since_audit = 0
        #: bounded stream of InvariantReport events (like maintenance_events)
        self.audit_events: List[dict] = []
        #: bounded retry-with-backoff for transient capacity-grow failures
        self.retry = RetryBudget()

    # ----------------------------------------------------- resilience plane
    def attach_wal(self, wal) -> "VersionedStoreBase":
        """Journal every applied batch through ``wal`` (fsync-before-
        dispatch); pair with ``save``/``resilience.recover`` for
        crash-exact recovery.  Returns self."""
        self.wal = wal
        return self

    def attach_audits(self, policy) -> "VersionedStoreBase":
        """Run pool invariant audits on the policy's cadence.  Returns
        self."""
        self.audits = policy
        return self

    def _wal_append(self, i_s, i_d, i_w, d_s, d_d):
        """Durably journal the canonical batch for version+1 (the version
        ``_record_batch`` will assign); returns the rollback token or
        None when no WAL is attached."""
        if self.wal is None:
            return None
        with obs.span("store.apply.wal", version=self.version):
            token = self.wal.append(self.version + 1, i_s, i_d, i_w,
                                    d_s, d_d)
        obs.inc("store.wal.appends")
        return token

    def audit(self, *, views=None, cross_view: bool = True):
        """Run the pool invariant audit now; returns the
        ``InvariantReport`` (also appended to ``audit_events``)."""
        from ..resilience.invariants import audit_store
        report = audit_store(self, views=views, cross_view=cross_view)
        self.audit_events.append(report.as_event())
        if len(self.audit_events) > self._log_capacity:
            self.audit_events = self.audit_events[-self._log_capacity:]
        return report

    def _auto_audit(self) -> None:
        """Epoch-close hook: audit on the AuditPolicy cadence."""
        if self.audits is None or not self.audits.every:
            return
        self._epochs_since_audit += 1
        if self._epochs_since_audit < self.audits.every:
            return
        self._epochs_since_audit = 0
        report = self.audit(views=self.audits.views,
                            cross_view=self.audits.cross_view)
        if not report.ok and self.audits.fail_fast:
            from ..resilience.invariants import InvariantViolationError
            raise InvariantViolationError(report)

    def _dump_postmortem(self, exc: BaseException) -> None:
        """Crash hook (apply's ``except BaseException``): write the
        black-box post-mortem bundle beside the WAL.  Best-effort and
        silent on the pipeline-recoverable classes — the exception itself
        still propagates to the caller either way."""
        from ..obs import postmortem
        postmortem.on_apply_failure(self, exc)

    def _resilience_meta(self) -> dict:
        """Host-side counters a checkpoint must carry so a recovered
        store's maintenance triggers replay exactly like the crashed
        process's would have (WAL replay determinism)."""
        return {"epochs_since_maint": int(self._epochs_since_maint),
                "deletes_since_maint": int(self._deletes_since_maint),
                "tombstone_base": int(self._tombstone_base),
                "last_reserve": {k: int(v)
                                 for k, v in self._last_reserve.items()}}

    def _adopt_resilience_meta(self, meta: dict) -> None:
        res = meta.get("resilience")
        if not res:
            return
        self._epochs_since_maint = int(res.get("epochs_since_maint", 0))
        self._deletes_since_maint = int(res.get("deletes_since_maint", 0))
        self._tombstone_base = int(res.get("tombstone_base", 0))
        self._last_reserve = {k: int(v)
                              for k, v in res.get("last_reserve",
                                                  {}).items()}

    def add_listener(self, fn: Callable[[AppliedBatch], None]) -> None:
        """Subscribe to applied batches (called with the epoch still open)."""
        self._listeners.append(fn)

    def batches_since(self, version: int) -> Optional[List[AppliedBatch]]:
        """Applied batches after ``version``, oldest first; None if the
        bounded log no longer reaches back that far."""
        if version == self.version:
            return []
        if version < self._log_floor:
            return None
        return [b for b in self._log if b.version > version]

    def _record_batch(self, **fields) -> AppliedBatch:
        """Bump the version, log the batch, notify listeners (epoch open)."""
        self.version += 1
        batch = AppliedBatch(version=self.version, **fields)
        self._log.append(batch)
        if len(self._log) > self._log_capacity:
            self._log = self._log[-self._log_capacity:]
            self._log_floor = self._log[0].version - 1
        if not batch.maintenance:
            self._deletes_since_maint += batch.n_deleted
        for fn in self._listeners:
            fn(batch)
        return batch

    # ----------------------------------------------------- maintenance plane
    def pool_stats(self, view: str = "forward") -> dict:
        raise NotImplementedError

    def _compact_view(self, view, policy, *, shrink: bool, slack_slabs: int):
        """(compacted view, CompactionReport) — per-store-kind hook."""
        raise NotImplementedError

    def _reclaim_view(self, view):
        """(reclaimed view, n_freed) — per-store-kind hook."""
        raise NotImplementedError

    def _maintain_views(self, action: str, policy, *, shrink: bool):
        """Apply one maintenance action to every live view (the loop is
        shared so the two store kinds cannot drift); returns
        ``(reports, reclaimed)`` keyed by view name."""
        reports: Dict[str, object] = {}
        reclaimed: Dict[str, int] = {}
        if action == "compact":
            for name in list(self._views):
                slack = max(policy.slack_slabs,
                            self._last_reserve.get(name, 0))
                self._views[name], reports[name] = self._compact_view(
                    self._views[name], policy, shrink=shrink,
                    slack_slabs=slack)
        elif action == "reclaim":
            for name in list(self._views):
                self._views[name], reclaimed[name] = self._reclaim_view(
                    self._views[name])
        else:
            raise ValueError(f"unknown maintenance action {action!r}")
        return reports, reclaimed

    def _cheap_stats(self) -> dict:
        """O(1) stand-in for ``pool_stats`` covering the triggers that need
        no pool scan.  Tombstone accounting is EXACT (every recorded delete
        mints one tombstone; only maintenance clears them); the scan-only
        fields are pinned to never-trigger values — a policy enabling those
        triggers takes the full-scan path instead.
        """
        tombs = self._tombstone_base + self._deletes_since_maint
        live = int(self.n_edges)
        return {"tombstone_ratio": tombs / max(1, tombs + live),
                "tombstone_lanes": tombs,
                "mean_chain": 0.0, "occupancy": 1.0, "dead_slabs": 0}

    def _auto_maintain(self) -> None:
        """Epoch-close hook: count the epoch, run the policy if present."""
        self._epochs_since_maint += 1
        if self.maintenance is not None:
            self.maintain()

    def maintain(self, action: Optional[str] = None):
        """Run pool maintenance across every view as ONE versioned unit.

        With ``action=None`` the store's ``MaintenancePolicy`` decides —
        from O(1) delete accounting when only the tombstone/every triggers
        are armed, from a full forward-view ``pool_stats`` scan when a
        chain/occupancy/dead-slab trigger needs it — and no-ops (returns
        None) without a trigger, so the per-epoch policy check costs no
        device transfer in the common case.  ``action="compact"`` /
        ``"reclaim"`` forces that tier.  On action: all views maintain
        together, the store version bumps, and listeners see a
        ``maintenance=True`` AppliedBatch — property states survive
        (vertex ids are stable); slab handles retained from before are
        stale and must be re-resolved via the reports' ``perm``.  Returns
        the ``MaintenanceRecord``.
        """
        import time as _time

        from .maintenance import MaintenancePolicy, MaintenanceRecord

        policy = self.maintenance or MaintenancePolicy()
        needs_scan = bool(policy.max_mean_chain or policy.min_occupancy
                          or policy.reclaim_dead_slabs)
        trigger = "forced"
        if action is None:
            stats = self.pool_stats() if needs_scan else self._cheap_stats()
            decision = policy.decide(
                stats, epochs_since=self._epochs_since_maint)
            if decision is None:
                return None
            action, trigger = decision
            if not needs_scan:           # a trigger fired: scan for shrink
                stats = self.pool_stats()
        else:
            stats = self.pool_stats()
        t0 = _time.time()
        with obs.span("store.maintain", version=self.version,
                      action=action, trigger=trigger) as sp:
            reports, reclaimed = self._maintain_views(
                action, policy, shrink=policy.allow_shrink(stats))
            sp.sync_on(self.views)
        self._epochs_since_maint = 0
        self._deletes_since_maint = 0
        # compaction drops every tombstone; reclamation only frees wholly
        # dead slabs — keep the (pre-pass, thus conservative) count.
        self._tombstone_base = (0 if action == "compact"
                                else stats["tombstone_lanes"])
        batch = self._record_batch(
            ins_src=None, ins_dst=None, ins_w=None, ins_mask=None,
            del_src=None, del_dst=None, del_mask=None,
            n_inserted=0, n_deleted=0, maintenance=True)
        fwd_report = reports.get(FORWARD)
        record = MaintenanceRecord(
            version=batch.version, action=action, trigger=trigger,
            reports=reports, reclaimed=reclaimed,
            duration_s=_time.time() - t0,
            tombstone_ratio=float(stats["tombstone_ratio"]),
            capacity_before=(fwd_report.old_capacity if fwd_report
                             else int(stats.get("capacity_slabs", 0))),
            capacity_after=(fwd_report.new_capacity if fwd_report
                            else int(stats.get("capacity_slabs", 0))),
            slabs_reclaimed=sum(reclaimed.values()))
        self.maintenance_count += 1
        self.last_maintenance = record
        # the structured per-pass event stream (bounded like the batch log)
        self.maintenance_events.append(record.as_event())
        if len(self.maintenance_events) > self._log_capacity:
            self.maintenance_events = \
                self.maintenance_events[-self._log_capacity:]
        obs.emit_event("maintenance", **record.as_event())
        obs.inc(f"store.maintain.{action}")
        _flight.record(_FL_MAINTAIN, batch.version,
                       record.slabs_reclaimed, record.capacity_after)
        return record


class GraphStore(VersionedStoreBase):
    """Forward + transposed + symmetric SlabGraph views as one versioned unit."""

    def __init__(self, views: Dict[str, SlabGraph], *, weighted: bool,
                 version: int = 0, log_capacity: int = 64,
                 maintenance=None):
        assert FORWARD in views, "a GraphStore always carries the forward view"
        unknown = set(views) - set(ALL_VIEWS)
        assert not unknown, f"unknown views {unknown}"
        super().__init__(version=version, log_capacity=log_capacity,
                         maintenance=maintenance)
        self._views = dict(views)
        self.weighted = bool(weighted)
        self._max_bpv = int(np.max(np.asarray(
            views[FORWARD].bucket_count))) if views[FORWARD].n_vertices else 1

    # ------------------------------------------------------------- construct
    @classmethod
    def from_edges(cls, n_vertices: int, src, dst, w=None, *,
                   hashing: bool = False, load_factor: float = 0.7,
                   slack_slabs: int = 0, with_transpose: bool = True,
                   with_symmetric: bool = True,
                   log_capacity: int = 64,
                   maintenance=None) -> "GraphStore":
        """Bulk-build every view from one host edge list (dedup shared)."""
        src, dst, w = dedup_pairs(src, dst, w)
        kw = dict(hashing=hashing, load_factor=load_factor,
                  slack_slabs=slack_slabs)
        views = {FORWARD: from_edges_host(n_vertices, src, dst, w, **kw)}
        if with_transpose:
            views[TRANSPOSE] = from_edges_host(n_vertices, dst, src, w, **kw)
        if with_symmetric:
            s2 = np.concatenate([src, dst])
            d2 = np.concatenate([dst, src])
            w2 = None if w is None else np.concatenate([w, w])
            views[SYMMETRIC] = from_edges_host(n_vertices, s2, d2, w2, **kw)
        return cls(views, weighted=w is not None, log_capacity=log_capacity,
                   maintenance=maintenance)

    # ------------------------------------------------------------- accessors
    @property
    def forward(self) -> SlabGraph:
        return self._views[FORWARD]

    @property
    def transpose(self) -> Optional[SlabGraph]:
        return self._views.get(TRANSPOSE)

    @property
    def symmetric(self) -> Optional[SlabGraph]:
        return self._views.get(SYMMETRIC)

    @property
    def views(self) -> Dict[str, SlabGraph]:
        return dict(self._views)

    @property
    def n_vertices(self) -> int:
        return self.forward.n_vertices

    @property
    def n_edges(self) -> int:
        return int(self.forward.n_edges)

    @property
    def out_degree(self) -> jnp.ndarray:
        """Device-resident out-degrees — the forward view's ``degree`` field."""
        return self.forward.degree

    @property
    def in_degree(self) -> jnp.ndarray:
        if self.transpose is None:
            raise ValueError("in-degrees live on the transpose view; build "
                             "the store with with_transpose=True")
        return self.transpose.degree

    @property
    def max_bpv(self) -> int:
        return self._max_bpv

    def sweep_rows(self, view: str = TRANSPOSE) -> Optional[int]:
        """Static sweep row bound for the analytics (``rows=``), or None
        without that view: the allocated prefix (``next_free``) rounded up
        to a sixteenth of the pool, so sweeps skip the pow2 capacity slack
        while a growing pool recompiles them at most 16 times per
        capacity.  Rows past ``next_free`` hold no edges: results are
        bit-identical to a full-pool sweep."""
        g = self._views.get(view)
        if g is None:
            return None
        cap = g.capacity_slabs
        step = max(256, cap // 16)
        return min(cap, -(-int(g.next_free) // step) * step)

    # ----------------------------------------------------------------- apply
    def apply(self, ins_src=None, ins_dst=None, ins_w=None,
              del_src=None, del_dst=None) -> AppliedBatch:
        """Apply one mixed update batch to every view; close the epoch.

        Deletions apply first, then insertions.  The batch is deduped and
        padded exactly once (``canonical_batch``); all live views mutate
        through one donated ``update_views`` dispatch.  Weighted stores
        default missing insert weights to 1.0.  Returns the
        ``AppliedBatch`` record (also appended to the catch-up log).

        Resilience plane (DESIGN.md §11): the RAW inputs are validated at
        admission (``QuarantinedBatch`` on corruption — nothing moved),
        the canonical batch journals to the attached WAL (fsync) before
        the donated dispatch, capacity growth runs under the store's
        ``RetryBudget``, and every phase carries a named fault point.
        """
        # admission guard FIRST, on the raw inputs: canonical_batch's
        # uint32 casts would silently wrap a negative/float id
        validate_batch(ins_src, ins_dst, ins_w, del_src, del_dst,
                       n_vertices=self.n_vertices)
        t0 = time.perf_counter()
        epoch_span = obs.span("store.apply", version=self.version)
        epoch_span.__enter__()
        try:
            with obs.span("store.apply.host_dedup"):
                i_s, i_d, i_w, d_s, d_d = canonical_batch(
                    ins_src, ins_dst, ins_w, del_src, del_dst,
                    weighted=self.weighted)
            faults.fault_point("apply.admitted", version=self.version)
            _flight.record(_FL_ADMIT, self.version, len(i_s), len(d_s))

            roles = tuple(v for v in ALL_VIEWS if v in self._views)

            # -- capacity (inserts allocate at most one slab per lane) ------
            if len(i_s):
                with obs.span("store.apply.capacity"):
                    p = _pow2(len(i_s))

                    def _grow():
                        faults.fault_point("store.capacity_grow",
                                           version=self.version)
                        for name in roles:
                            need = (2 * p + 64 if name == SYMMETRIC
                                    else p + 64)
                            self._views[name] = ensure_capacity(
                                self._views[name], need)
                            self._last_reserve[name] = need
                        _flight.record(_FL_GROW, self.version, p)

                    run_with_retries(_grow, budget=self.retry,
                                     site="store.capacity_grow")

            # -- canonical device batches (every view derives from these) ---
            del_sj = del_dj = del_mask = None
            ins_sj = ins_dj = ins_wj = ins_mask = None
            dels = ins = None
            if len(d_s):
                p = _pow2(len(d_s))
                del_sj, del_dj = _pad_u32(d_s, p), _pad_u32(d_d, p)
                dels = (del_sj, del_dj)
            if len(i_s):
                p = _pow2(len(i_s))
                ins_sj, ins_dj = _pad_u32(i_s, p), _pad_u32(i_d, p)
                ins_wj = _pad_f32(i_w, p)
                ins = (ins_sj, ins_dj, ins_wj)

            # -- durability: journal the canonical batch, THEN dispatch -----
            wal_token = self._wal_append(i_s, i_d, i_w, d_s, d_d)
            faults.fault_point("apply.post_wal", version=self.version)
            _flight.record(_FL_POST_WAL, self.version,
                           0 if wal_token is None else 1)

            try:
                # -- single stacked engine dispatch over every live view ----
                n_inserted = n_deleted = 0
                if ins is not None or dels is not None:
                    with obs.span("store.apply.dispatch",
                                  version=self.version,
                                  views=len(roles)) as sp:
                        new_views, ins_mask, del_mask = update_views(
                            tuple(self._views[r] for r in roles), roles,
                            ins, dels)
                        for r, g in zip(roles, new_views):
                            self._views[r] = g
                        # the applied counts and the probe counters that
                        # update_views put on this span: one transfer
                        n_deleted, n_inserted = (int(n) for n in sp.fetch(
                            tuple(0 if m is None else
                                  jnp.sum(m.astype(jnp.int32))
                                  for m in (del_mask, ins_mask))))
                faults.fault_point("apply.pre_close", version=self.version)
                _flight.record(_FL_DISPATCH, self.version,
                               n_inserted, n_deleted)

                # -- version bump + notification (epoch still open) ---------
                with obs.span("store.apply.notify"):
                    batch = self._record_batch(
                        ins_src=ins_sj, ins_dst=ins_dj, ins_w=ins_wj,
                        ins_mask=ins_mask, del_src=del_sj, del_dst=del_dj,
                        del_mask=del_mask,
                        n_inserted=n_inserted, n_deleted=n_deleted)

                # -- close the epoch on every view --------------------------
                with obs.span("store.apply.epoch_close",
                              sync=tuple(self._views.values())):
                    for name, g in self._views.items():
                        self._views[name] = update_slab_pointers(g)
                faults.fault_point("apply.post_close", version=self.version)
                _flight.record(_FL_CLOSE, batch.version,
                               n_inserted, n_deleted)
            except faults.InjectedCrash:
                raise          # a simulated kill: the WAL record survives
            except BaseException:
                # the journaled batch never applied in THIS process and the
                # caller sees the failure — drop the record so a later
                # recovery replay doesn't resurrect a rejected batch
                if wal_token is not None:
                    self.wal.rollback(wal_token)
                raise

            epoch_span.annotate(inserted=n_inserted, deleted=n_deleted)
        except BaseException as e:
            # the black box: dump a post-mortem bundle beside the WAL at
            # the moment of death (never raises, skips recoverable kinds)
            self._dump_postmortem(e)
            raise
        finally:
            epoch_span.__exit__(None, None, None)
        if obs.metrics.enabled():
            obs.observe("store.apply", time.perf_counter() - t0)
            obs.inc("store.apply.epochs")
            obs.inc("store.apply.inserted", n_inserted)
            obs.inc("store.apply.deleted", n_deleted)

        # -- maintenance + audit planes: policy checks on the closed epoch --
        self._auto_maintain()
        self._auto_audit()
        return batch

    # ----------------------------------------------------- maintenance plane
    def pool_stats(self, view: str = FORWARD) -> dict:
        """Pool-health snapshot of one view (``core.pool_stats``)."""
        from ..core.slab_graph import pool_stats as _pool_stats
        return _pool_stats(self._views[view])

    def _compact_view(self, g: SlabGraph, policy, *, shrink: bool,
                      slack_slabs: int):
        from ..kernels.slab_compact import compact
        return compact(g, impl=policy.impl, shrink=shrink,
                       slack_slabs=slack_slabs)

    def _reclaim_view(self, g: SlabGraph):
        from ..kernels.slab_compact import reclaim_free_slabs
        return reclaim_free_slabs(g)

    # --------------------------------------------------------------- queries
    def query(self, src, dst) -> np.ndarray:
        """Batched edge-membership against the forward view (host arrays in,
        host bool array out, trimmed to the query length)."""
        with obs.span("store.query", version=self.version) as sp:
            src = np.asarray(src, np.uint32)
            dst = np.asarray(dst, np.uint32)
            p = _pow2(max(len(src), 1))
            found = query_edges(self.forward, _pad_u32(src, p),
                                _pad_u32(dst, p))
            # the answer and the probe's counters: one transfer
            return np.asarray(sp.fetch(found))[:len(src)]

    def neighbors(self, vertices, *, out_capacity: int = 4096
                  ) -> EdgeFrontier:
        """Current out-edges of ``vertices`` (forward view) as an EdgeFrontier."""
        vertices = np.asarray(vertices, np.uint32)
        p = _pow2(max(len(vertices), 1))
        verts = _pad_u32(vertices, p)
        vmask = jnp.asarray(np.arange(p) < len(vertices))
        return expand_vertices(self.forward, verts, vmask,
                               out_capacity=_pow2(out_capacity),
                               max_bpv=self._max_bpv)

    # ------------------------------------------------------------ checkpoint
    def save(self, ckpt_dir, step: Optional[int] = None, *, registry=None,
             extra: Optional[dict] = None, keep_last: int = 3):
        """Persist all views (+ registered property states) atomically.

        The manifest's ``extra`` carries everything ``restore`` needs to
        rebuild the pytree structure: per-view bucket metadata, the store
        version, and per-property versions.
        """
        from ..checkpoint import ckpt
        step = self.version if step is None else int(step)
        props = {} if registry is None else registry.states()
        prop_versions = {} if registry is None else registry.versions()
        meta = {
            "stream_store": True,
            "version": int(self.version),
            "n_vertices": int(self.n_vertices),
            "weighted": bool(self.weighted),
            "views": {name: int(g.n_buckets)
                      for name, g in self._views.items()},
            "prop_versions": {k: int(v) for k, v in prop_versions.items()},
            "resilience": self._resilience_meta(),
        }
        if extra:
            meta.update(extra)
        path = ckpt.save(ckpt_dir, step, {"views": dict(self._views),
                                          "props": props}, extra=meta,
                         keep_last=keep_last)
        # the checkpoint now covers every journaled batch up to this
        # version: retire the WAL segments it subsumes
        if self.wal is not None and step == self.version:
            self.wal.truncate(self.version)
        return path

    @classmethod
    def restore(cls, ckpt_dir, *, step: Optional[int] = None,
                specs: Sequence = (), policies: Optional[Dict[str, str]] = None,
                log_capacity: int = 64, maintenance=None):
        """Rebuild (store, registry) from a checkpoint.

        ``specs`` must cover every property saved in the checkpoint (their
        ``state_like`` builds the restore skeleton; their maintainers resume
        from the saved states + versions).  Returns ``(store, registry)``;
        the registry is None when the checkpoint carried no properties and
        no specs were given.  ``maintenance=`` re-attaches the policy the
        crashed process ran — its trigger counters are restored from the
        manifest, so a WAL replay re-derives maintenance epochs exactly.
        """
        from ..checkpoint import ckpt
        from ..checkpoint.ckpt import CheckpointError
        manifest = ckpt.read_manifest(ckpt_dir, step=step)
        meta = manifest["extra"]
        missing = [k for k in ("n_vertices", "weighted", "views",
                               "prop_versions")
                   if not meta.get("stream_store") or k not in meta]
        if missing or not meta.get("stream_store"):
            raise CheckpointError(
                f"{ckpt_dir} step {manifest['step']} is not a GraphStore "
                f"checkpoint (missing meta: "
                f"{missing or ['stream_store']}) — it was saved by a "
                "different layer or its manifest is from an incompatible "
                "version; pick another step= or re-checkpoint")
        V = int(meta["n_vertices"])
        weighted = bool(meta["weighted"])

        def view_like(n_buckets: int) -> SlabGraph:
            bc = np.zeros(V, np.int32)
            bc[0] = n_buckets
            return empty(V, bc, n_buckets + 1, weighted=weighted)

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
        store = cls(tree["views"], weighted=weighted,
                    version=meta["version"], log_capacity=log_capacity,
                    maintenance=maintenance)
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

"""Typed requests + batched execution pipeline for `repro.stream`.

The request plane of the streaming service: updates and queries arrive as
typed records, and the pipeline turns a request sequence into the minimum
number of device calls:

* consecutive ``UpdateBatch`` requests coalesce (net-effect per edge: the
  LAST operation on a pair wins, matching sequential application) into one
  ``GraphStore.apply`` — one epoch, one capacity check, one notification,
* consecutive ``MembershipQuery`` requests merge into one ``query_edges``
  call and split back per-request,
* ``PropertyRead`` hits the registry (lazy properties catch up here —
  queries only pay for the properties they read).

Every request gets a ``Response`` carrying the store version it observed.

Overload safety (DESIGN.md §11): malformed requests and recoverable apply
failures (``QuarantinedBatch``, ``RetryExhausted``) come back as structured
``kind="error"`` responses — the pipeline keeps serving the rest of the
sequence.  An optional :class:`~repro.resilience.CircuitBreaker` sheds
update groups after K consecutive apply failures while reads keep working;
while the breaker is open, ``PropertyRead`` degrades to the registry's
``peek`` — a version-tagged, possibly-stale state — instead of forcing a
catch-up replay through a store that is failing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .. import obs
from ..obs import flight as _flight
from ..obs import postmortem as _postmortem
from ..resilience.faults import InjectedCrash
from ..resilience.guard import PIPELINE_RECOVERABLE, CircuitBreaker, \
    QuarantinedBatch
from .properties import PropertyRegistry
from .store import GraphStore

# one interned flight code per request class: the black box records every
# served request (class, latency ns, group size) even with metrics off
_FL_REQ = {k: _flight.intern(f"pipeline.{k}")
           for k in ("update", "member", "neighbors", "property",
                     "error", "shed")}


# ---------------------------------------------------------------------------
# request / response records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UpdateBatch:
    """Mixed edge update: deletions apply before insertions (store contract)."""
    ins_src: Any = ()
    ins_dst: Any = ()
    ins_w: Any = None
    del_src: Any = ()
    del_dst: Any = ()


@dataclasses.dataclass(frozen=True)
class MembershipQuery:
    src: Any
    dst: Any


@dataclasses.dataclass(frozen=True)
class NeighborsQuery:
    vertices: Any
    out_capacity: int = 4096


@dataclasses.dataclass(frozen=True)
class PropertyRead:
    name: str


Request = Union[UpdateBatch, MembershipQuery, NeighborsQuery, PropertyRead]


@dataclasses.dataclass
class Response:
    kind: str
    version: int
    payload: Dict[str, Any]
    latency_s: float


# ---------------------------------------------------------------------------
# update coalescing
# ---------------------------------------------------------------------------

def coalesce_updates(batches: Sequence[UpdateBatch]) -> UpdateBatch:
    """Net a run of update batches into one equivalent batch.

    Sequential semantics: within one batch deletions precede insertions, and
    batches apply in order — so per edge the LAST operation in that flattened
    sequence decides whether it ends up inserted or deleted.  Weights ride
    along with their insert; an edge deleted and later re-inserted stays in
    the delete list too (``apply`` deletes first), so the re-insert lands its
    new weight instead of being rejected against the still-present edge.
    """
    srcs, dsts, ws, ops = [], [], [], []
    for b in batches:
        d_s = np.asarray(b.del_src, np.uint32)
        if len(d_s):
            srcs.append(d_s)
            dsts.append(np.asarray(b.del_dst, np.uint32))
            ws.append(np.zeros(len(d_s), np.float32))
            ops.append(np.zeros(len(d_s), np.int8))
        i_s = np.asarray(b.ins_src, np.uint32)
        if len(i_s):
            srcs.append(i_s)
            dsts.append(np.asarray(b.ins_dst, np.uint32))
            ws.append(np.ones(len(i_s), np.float32) if b.ins_w is None
                      else np.asarray(b.ins_w, np.float32))
            ops.append(np.ones(len(i_s), np.int8))
    if not srcs:
        return UpdateBatch()
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    w = np.concatenate(ws)
    op = np.concatenate(ops)
    key = (src.astype(np.uint64) << np.uint64(32)) | dst.astype(np.uint64)
    order = np.argsort(key, kind="stable")      # stable: sequence order kept
    k_s = key[order]
    start = np.ones(len(k_s), bool)
    start[1:] = k_s[1:] != k_s[:-1]
    last = np.ones(len(k_s), bool)
    last[:-1] = start[1:]                       # last occurrence per edge
    take = order[last]
    ins = op[take] == 1
    had_del = np.minimum.reduceat(op[order], np.nonzero(start)[0]) == 0
    has_w = any(b.ins_w is not None for b in batches)
    deleted = ~ins | (ins & had_del)            # re-inserts delete first
    return UpdateBatch(
        ins_src=src[take][ins], ins_dst=dst[take][ins],
        ins_w=w[take][ins] if has_w else None,
        del_src=src[take][deleted], del_dst=dst[take][deleted])


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

class RequestPipeline:
    """Executes a request sequence against (store, registry) with coalescing
    and query batching; responses align 1:1 with the input requests."""

    def __init__(self, store: GraphStore,
                 registry: Optional[PropertyRegistry] = None, *,
                 coalesce: bool = True, batch_membership: bool = True,
                 breaker: Optional[CircuitBreaker] = None,
                 health=None, health_every: int = 16):
        self.store = store
        self.registry = registry
        self.coalesce = coalesce
        self.batch_membership = batch_membership
        # optional overload valve: updates shed while open, reads degrade
        # to version-tagged stale serves (None = fail per-request only)
        self.breaker = breaker
        # optional obs.health.HealthEngine: every served request feeds it,
        # and every ``health_every`` dispatches it evaluates a report —
        # fed to the breaker (burn-rate shedding) when one is armed
        self.health = health
        self.health_every = int(health_every)
        self._since_health = 0
        # the ``req`` tag of the pipeline's spans: one number per
        # dispatched request group, shared by the spans it nests
        self._req = 0
        if breaker is not None:
            # post-mortem bundles carry the breaker state at death
            _postmortem.register_breaker(breaker)

    # -- group runners ------------------------------------------------------
    def _apply_updates(self, group: List[UpdateBatch]) -> Dict[str, Any]:
        net = group[0] if len(group) == 1 else coalesce_updates(group)
        applied = self.store.apply(net.ins_src, net.ins_dst, net.ins_w,
                                   net.del_src, net.del_dst)
        return {"inserted": applied.n_inserted, "deleted": applied.n_deleted,
                "coalesced": len(group)}

    def _run_membership(self, group: List[MembershipQuery]) -> List[dict]:
        src = np.concatenate([np.asarray(q.src, np.uint32) for q in group])
        dst = np.concatenate([np.asarray(q.dst, np.uint32) for q in group])
        found = self.store.query(src, dst)
        out, at = [], 0
        for q in group:
            n = len(np.asarray(q.src))
            out.append({"found": found[at:at + n],
                        "hits": int(found[at:at + n].sum()),
                        "merged": len(group)})
            at += n
        return out

    # -- telemetry ----------------------------------------------------------
    def _observe(self, kind: str, dt: float, group: int = 1, *,
                 cls: Optional[str] = None, ok: bool = True) -> None:
        """Per-request-class latency histogram + coalescing accounting.
        The flight recorder and the health engine are fed FIRST — both run
        with metrics off (``cls`` names the SLO class when ``kind`` is an
        outcome like ``error``/``shed``)."""
        _flight.record(_FL_REQ[kind], int(1e9 * dt), group)
        if self.health is not None:
            self.health.observe_request(cls or kind, dt, ok=ok)
            self._since_health += 1
            if self._since_health >= self.health_every:
                self._since_health = 0
                self.health.observe_store(self.store)
                if self.registry is not None:
                    self.health.observe_staleness(self.registry)
                report = self.health.report()
                if self.breaker is not None:
                    self.breaker.note_health(report)
        if not obs.metrics.enabled():
            return
        obs.observe(f"pipeline.latency.{kind}", dt)
        obs.inc(f"pipeline.requests.{kind}", group)
        obs.inc(f"pipeline.dispatches.{kind}")
        if group > 1:
            obs.inc(f"pipeline.coalesced.{kind}", group - 1)

    def _fail(self, kind: str, exc: BaseException, dt: float) -> Response:
        """Structured error Response for one recoverable failure."""
        payload: Dict[str, Any] = {"error": type(exc).__name__,
                                   "detail": str(exc)}
        if isinstance(exc, QuarantinedBatch):
            payload["reasons"] = exc.reasons
        obs.inc(f"pipeline.errors.{kind}")
        return Response("error", self.store.version, payload, dt)

    # -- driver -------------------------------------------------------------
    def run(self, requests: Sequence[Request]) -> List[Response]:
        responses: List[Optional[Response]] = [None] * len(requests)
        i = 0
        while i < len(requests):
            r = requests[i]
            j = i + 1
            self._req += 1
            req = self._req
            if isinstance(r, UpdateBatch):
                while (self.coalesce and j < len(requests)
                       and isinstance(requests[j], UpdateBatch)):
                    j += 1
                t0 = time.perf_counter()
                if self.breaker is not None and not self.breaker.allow():
                    self.breaker.shed()
                    dt = time.perf_counter() - t0
                    self._observe("shed", dt, j - i, cls="update", ok=False)
                    payload = {"error": "circuit_open", "shed": True,
                               "breaker": self.breaker.status()}
                    for k in range(i, j):
                        responses[k] = Response("error", self.store.version,
                                                payload, dt)
                    i = j
                    continue
                try:
                    with obs.span("pipeline.update", req=req,
                                  coalesced=j - i):
                        payload = self._apply_updates(list(requests[i:j]))
                except InjectedCrash:
                    raise                # simulated kill: nothing catches it
                except PIPELINE_RECOVERABLE as e:
                    if self.breaker is not None:
                        self.breaker.record_failure()
                    dt = time.perf_counter() - t0
                    self._observe("error", dt, j - i, cls="update",
                                  ok=False)
                    resp = self._fail("update", e, dt)
                    for k in range(i, j):
                        responses[k] = resp
                    i = j
                    continue
                if self.breaker is not None:
                    self.breaker.record_success()
                dt = time.perf_counter() - t0
                self._observe("update", dt, j - i)
                for k in range(i, j):
                    responses[k] = Response("update", self.store.version,
                                            payload, dt)
            elif isinstance(r, MembershipQuery):
                while (self.batch_membership and j < len(requests)
                       and isinstance(requests[j], MembershipQuery)):
                    j += 1
                t0 = time.perf_counter()
                with obs.span("pipeline.member", req=req, merged=j - i):
                    payloads = self._run_membership(list(requests[i:j]))
                dt = time.perf_counter() - t0
                self._observe("member", dt, j - i)
                for k, p in zip(range(i, j), payloads):
                    responses[k] = Response("member", self.store.version,
                                            p, dt)
            elif isinstance(r, NeighborsQuery):
                t0 = time.perf_counter()
                with obs.span("pipeline.neighbors", req=req):
                    ef = self.store.neighbors(r.vertices,
                                              out_capacity=r.out_capacity)
                    n = int(ef.size)
                    payload = {"src": np.asarray(ef.src)[:n],
                               "dst": np.asarray(ef.dst)[:n],
                               "count": n, "overflow": bool(ef.overflow)}
                dt = time.perf_counter() - t0
                self._observe("neighbors", dt)
                responses[i] = Response("neighbors", self.store.version,
                                        payload, dt)
            elif isinstance(r, PropertyRead):
                t0 = time.perf_counter()
                if self.registry is None:
                    responses[i] = Response(
                        "error", self.store.version,
                        {"error": "no_registry",
                         "detail": "PropertyRead requires a "
                                   "PropertyRegistry"},
                        time.perf_counter() - t0)
                elif self.breaker is not None and self.breaker.state == "open":
                    # degraded serving: the store is shedding writes — do
                    # NOT force a catch-up replay through it; serve the
                    # last good state, tagged with the version it is valid
                    # for so callers can see the staleness.
                    value, version = self.registry.peek(r.name)
                    dt = time.perf_counter() - t0
                    self._observe("property", dt)
                    obs.inc("pipeline.stale_reads")
                    responses[i] = Response(
                        "property", version,
                        {"name": r.name, "value": value, "stale": True,
                         "staleness": self.store.version - version}, dt)
                else:
                    with obs.span("pipeline.property", req=req,
                                  prop=r.name) as sp:
                        value = self.registry.read(r.name)
                        sp.sync_on(value)
                    dt = time.perf_counter() - t0
                    self._observe("property", dt)
                    responses[i] = Response("property", self.store.version,
                                            {"name": r.name, "value": value},
                                            dt)
            else:
                # an unknown request must not take the whole sequence down:
                # answer it with a structured error and keep serving.
                obs.inc("pipeline.errors.unknown_request")
                responses[i] = Response(
                    "error", self.store.version,
                    {"error": "unknown_request",
                     "detail": f"unsupported request type "
                               f"{type(r).__name__}",
                     "request": type(r).__name__}, 0.0)
            i = j
        return responses

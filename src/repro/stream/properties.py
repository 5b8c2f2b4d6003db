"""Incremental-property registry — the query plane of `repro.stream`.

Analytics (PageRank / BFS / SSSP / WCC) register ``{init, on_batch, refresh}``
maintainers (the ``stream_property`` hooks exported by each algorithm module)
keyed to GraphStore versions.  Two maintenance policies:

* ``eager`` — the maintainer runs inside ``GraphStore.apply`` while the update
  epoch is still open (required for maintainers that read the UpdateIterator
  state; it is cleared when the epoch closes).
* ``lazy``  — invalidation only: the state is caught up on first read by
  replaying the store's batch log through ``on_batch``; if the bounded log has
  been truncated past the property's version, or the property lags by more
  epochs than its ``max_replay``, ``refresh`` (static recompute) runs
  instead.  Queries only pay for the properties they read.

``state_like(n_vertices)`` builds a cheap structural skeleton of the state
pytree so checkpoints restore without recomputing anything.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

from .. import obs
from .store import AppliedBatch, GraphStore

EAGER = "eager"
LAZY = "lazy"
_UNSET = object()


@dataclasses.dataclass(frozen=True)
class PropertySpec:
    """An incremental maintainer: how to build, advance, and rebuild a
    per-graph property (any pytree) kept consistent with a GraphStore.

    ``collapse_replay`` declares ``on_batch`` batch-independent (it only
    reads the current graph, e.g. warm-started PageRank): lazy catch-up
    then runs it ONCE instead of once per missed epoch.

    ``max_replay`` bounds lazy catch-up: a property that lags by more
    (non-maintenance) epochs than this is refreshed instead of replayed.
    Set it where replaying one epoch costs about as much as ``refresh``
    (None = always replay while the log covers the lag).
    """
    name: str
    init: Callable[[GraphStore], Any]
    on_batch: Callable[[GraphStore, Any, AppliedBatch], Any]
    refresh: Callable[[GraphStore], Any]
    state_like: Optional[Callable[[int], Any]] = None
    collapse_replay: bool = False
    max_replay: Optional[int] = None


@dataclasses.dataclass
class _Entry:
    spec: PropertySpec
    policy: str
    state: Any
    version: int


class PropertyRegistry:
    """Versioned property states over one GraphStore.

    Subscribes to the store's applied-batch stream on construction; eager
    entries advance inside every ``apply``, lazy entries advance on ``read``.
    """

    def __init__(self, store: GraphStore):
        self.store = store
        self._entries: Dict[str, _Entry] = {}
        store.add_listener(self._on_batch)

    # ---------------------------------------------------------------- admin
    def register(self, spec: PropertySpec, *, policy: str = LAZY,
                 _state: Any = _UNSET, _version: Optional[int] = None) -> None:
        """Register a maintainer.  ``_state``/``_version`` adopt a restored
        checkpoint state instead of running ``init`` (see GraphStore.restore).
        """
        assert policy in (EAGER, LAZY), policy
        if spec.name in self._entries:
            raise KeyError(f"property {spec.name!r} already registered")
        if _state is _UNSET:
            state, version = spec.init(self.store), self.store.version
        else:
            state, version = _state, int(_version)
        self._entries[spec.name] = _Entry(spec, policy, state, version)

    def names(self):
        return list(self._entries)

    def states(self) -> Dict[str, Any]:
        """Current states WITHOUT catch-up (pair with ``versions`` when
        persisting — a lazy state is valid *for its recorded version*)."""
        return {name: e.state for name, e in self._entries.items()}

    def versions(self) -> Dict[str, int]:
        return {name: e.version for name, e in self._entries.items()}

    def status(self) -> Dict[str, dict]:
        return {name: {"policy": e.policy, "version": e.version,
                       "stale": e.version < self.store.version}
                for name, e in self._entries.items()}

    # ----------------------------------------------------------- maintenance
    def _on_batch(self, batch: AppliedBatch) -> None:
        for e in self._entries.values():
            if e.policy == EAGER:
                if batch.maintenance:
                    # compaction/reclamation changes no edges and vertex
                    # ids are stable: the state is already consistent with
                    # the new version — just re-anchor it.
                    if e.version == batch.version - 1:
                        e.version = batch.version
                    continue
                # an eager entry is always exactly one batch behind here
                e.state = e.spec.on_batch(self.store, e.state, batch)
                e.version = batch.version

    def _catch_up(self, e: _Entry) -> None:
        if e.version == self.store.version:
            return
        missed = self.store.batches_since(e.version)
        if missed is not None:
            # maintenance epochs are replay no-ops (edge set unchanged)
            missed = [b for b in missed if not b.maintenance]
        name = e.spec.name
        limit = e.spec.max_replay
        if missed is None or (limit is not None and len(missed) > limit):
            # log truncated past the property's version, or a replay
            # dearer than one static recompute
            with obs.span("property.refresh", prop=name) as sp:
                e.state = e.spec.refresh(self.store)
                sp.sync_on(e.state)
            obs.inc(f"property.{name}.refresh")
        elif e.spec.collapse_replay and missed:
            with obs.span("property.replay", prop=name, collapsed=True,
                          depth=len(missed)) as sp:
                e.state = e.spec.on_batch(self.store, e.state, missed[-1])
                sp.sync_on(e.state)
            obs.inc(f"property.{name}.replay_collapsed")
            obs.observe(f"property.replay_depth.{name}", len(missed))
        else:
            with obs.span("property.replay", prop=name,
                          depth=len(missed)) as sp:
                for batch in missed:
                    e.state = e.spec.on_batch(self.store, e.state, batch)
                sp.sync_on(e.state)
            obs.inc(f"property.{name}.replay", max(1, len(missed)))
            obs.observe(f"property.replay_depth.{name}", len(missed))
        e.version = self.store.version

    def read(self, name: str) -> Any:
        """The property state, consistent with the store's current version."""
        e = self._entries[name]
        if obs.metrics.enabled():
            # staleness at read: epochs this property lags the store by
            obs.observe(f"property.staleness.{name}",
                        self.store.version - e.version)
        self._catch_up(e)
        return e.state

    def peek(self, name: str) -> Tuple[Any, int]:
        """``(state, version)`` as-is — NO catch-up, no device work.

        The degraded-mode read: while the pipeline's circuit breaker is
        open (store unhealthy), ``PropertyRead`` serves this version-tagged
        possibly-stale state instead of forcing a replay through a store
        that is failing."""
        e = self._entries[name]
        return e.state, e.version

    def refresh(self, name: str) -> Any:
        """Force a static recompute (also re-anchors the version)."""
        e = self._entries[name]
        e.state = e.spec.refresh(self.store)
        e.version = self.store.version
        return e.state

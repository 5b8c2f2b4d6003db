"""Dispatch layer for the slab-sweep engine: SlabGraph in, per-vertex out.

``sweep_partials`` runs the fused gather–combine–reduce over the pool and
returns per-slab partials; ``sweep_vertices`` folds those into per-vertex
outputs with a ``segment_sum``/``segment_min`` keyed by ``slab_vertex`` —
together they are the whole super-step data path of PageRank (sum), WCC
label propagation (min), and SSSP/BFS relaxation (min-plus / arg-min-plus).

Implementation selection (``impl``):

  * ``"pallas"`` — the fused Pallas kernel, interpret mode only (for
    validation): the TPU v5e compiler refuses its ``values_ref[idx]``
    gather (``Cannot do int indexing on TPU``), so a compiled call on a TPU
    raises that error.
  * ``"ref"``    — the pure-jnp engine, itself a single fused XLA
    gather+reduce (no ``EdgeFrontier`` materialization, no cumsum+scatter
    compaction).
  * ``"auto"``   — ``"ref"`` on every backend (``repro.kernels.resolve_impl``).

Both implementations are lane-for-lane identical (integer/min semirings
bit-exact; sums share the same lane-axis reduction order).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.slab_graph import SlabGraph
from ...obs import timed_dispatch
from .. import resolve_impl
from .kernel import slab_sweep_pallas
from .ref import SEMIRINGS, slab_sweep_ref

_MIN_FAMILY = ("min", "min_plus", "arg_min_plus")


def slice_rows(g: SlabGraph, rows: Optional[int],
               rows_per_block: int = 256) -> SlabGraph:
    """Statically bound the sweep to the first ``rows`` pool rows.

    ``rows`` is a host-known upper bound on the allocated region (max
    ``next_free`` across shards, e.g. the sharded store's high-water
    accounting).  Rows past ``next_free`` hold no live keys
    (``slab_vertex == -1``, EMPTY lanes), so dropping them leaves every
    semiring result bit-identical while the gather/reduce shrinks from
    pool capacity to the allocated prefix.  The bound is rounded up to a
    ``rows_per_block`` multiple so the Pallas grid stays whole-block.
    """
    if rows is None:
        return g
    rows = -(-int(rows) // rows_per_block) * rows_per_block
    if rows >= g.keys.shape[0]:
        return g
    import dataclasses
    return dataclasses.replace(
        g, keys=g.keys[:rows], slab_vertex=g.slab_vertex[:rows],
        weights=None if g.weights is None else g.weights[:rows])


def _resolve(impl: str, interpret: Optional[bool]):
    return resolve_impl(impl, interpret, xla="ref",
                        impls=("pallas", "ref"))


@timed_dispatch("slab_sweep")
def sweep_partials(g: SlabGraph, values: jnp.ndarray, *, semiring: str,
                   frontier: Optional[jnp.ndarray] = None,
                   target: Optional[jnp.ndarray] = None,
                   weighted: Optional[bool] = None,
                   n_keys: Optional[int] = None,
                   impl: str = "auto", rows_per_block: int = 256,
                   rows: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """(S,) semiring partials over the pool.

    ``frontier`` is a (V,) bool bitmask over *key* vertices (None = all
    active).  ``target`` for ``arg_min_plus`` is per-vertex (V,) and is
    gathered to the slab rows here.  ``weighted`` defaults to using the
    weight pool exactly for the ``*_plus`` semirings on weighted graphs
    (unit weight otherwise) — pass explicitly to weight a ``sum`` sweep.
    ``n_keys`` bounds lane-key validity and defaults to ``g.n_vertices``;
    the sharded plane stores GLOBAL neighbor ids in shard-local pools, so
    it passes the global vertex count here (``values``/``frontier`` are
    then global vectors while the owner axis stays shard-local).
    ``rows`` (static) bounds the sweep to the allocated pool prefix —
    see ``slice_rows``; results are bit-identical to the full sweep.
    This entry point is shard_map-compatible: called on a shard-local
    ``SlabGraph`` block inside a ``shard_map`` body it traces per-shard
    collective-free code (the sharded plane composes it with
    ``all_gather``/``psum`` exchanges).
    """
    if semiring not in SEMIRINGS:
        raise ValueError(f"unknown semiring {semiring!r}")
    g = slice_rows(g, rows, rows_per_block)
    if weighted is None:
        weighted = g.weighted and semiring in ("min_plus", "arg_min_plus")
    weights = g.weights if weighted else None
    if n_keys is None:
        n_keys = g.n_vertices
    if target is not None:
        # per-vertex target → per-slab scalar (owner is uniform per row)
        target = target[jnp.maximum(g.slab_vertex, 0)]
    impl, interpret = _resolve(impl, interpret)
    if impl == "pallas":
        return slab_sweep_pallas(g.keys, g.slab_vertex, values, weights,
                                 frontier, target, semiring=semiring,
                                 n_vertices=n_keys,
                                 rows_per_block=rows_per_block,
                                 interpret=interpret)
    return slab_sweep_ref(g.keys, g.slab_vertex, values, semiring=semiring,
                          n_vertices=n_keys, weights=weights,
                          frontier=frontier, target=target)


@timed_dispatch("slab_sweep")
def sweep_vertices(g: SlabGraph, values: jnp.ndarray, *, semiring: str,
                   frontier: Optional[jnp.ndarray] = None,
                   target: Optional[jnp.ndarray] = None,
                   weighted: Optional[bool] = None,
                   n_keys: Optional[int] = None,
                   impl: str = "auto", rows_per_block: int = 256,
                   rows: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """(V,) per-vertex semiring reduction: partials folded over slab_vertex.

    Output lands at the slab *owner* (the pull direction): run on the
    in-edge/transposed graph for push-style relaxations — see DESIGN.md §3.
    On sharded pools the output stays shard-local ((n_local,) per shard)
    while ``n_keys`` widens the gather to the global id space.  ``rows``
    statically bounds the sweep to the allocated prefix (bit-identical —
    sliced-out rows contribute only semiring identities); shard_map-safe
    like ``sweep_partials``.
    """
    g = slice_rows(g, rows, rows_per_block)
    partials = sweep_partials(g, values, semiring=semiring, frontier=frontier,
                              target=target, weighted=weighted, n_keys=n_keys,
                              impl=impl, rows_per_block=rows_per_block,
                              interpret=interpret)
    seg = jnp.where(g.slab_vertex >= 0, g.slab_vertex, g.n_vertices)
    reduce = (jax.ops.segment_sum if semiring == "sum"
              else jax.ops.segment_min)
    return reduce(partials, seg, num_segments=g.n_vertices + 1)[:g.n_vertices]


__all__ = ["sweep_partials", "sweep_vertices", "slice_rows",
           "slab_sweep_pallas", "slab_sweep_ref", "SEMIRINGS"]

"""Dynamic PageRank (paper §4.1, Algs. 5, 13, 14).

The graph object stores *in*-edges (slab owner = destination vertex, lane
keys = source vertices), exactly as the paper's Compute kernel consumes them;
``out_degree`` travels separately.

Per super-step:
  1. ``FindContributionPerVertex``: contrib[u] = PR[u]/out[u] — one coalesced
     pass (the paper's divergence-reduction caching trick ports verbatim).
  2. ``Compute``: for every vertex, sum contrib over in-neighbors.  On TPU
     this is THE slab-pool sweep: gather contrib at every pool lane, mask
     invalid lanes, reduce lanes per slab, ``segment_sum`` per vertex — the
     hot loop the ``slab_pagerank`` Pallas kernel implements.
  3. ``FindTeleportProb``: zero-out-degree mass redistributed (Alg. 13).
  4. L1 delta against the previous vector; iterate to convergence.

Dynamic (incremental == decremental, paper §6.2.2): warm-start from the
previous PageRank vector after the batch mutates the graph — convergence takes
the hit only where mass actually moved.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.hashing import SLAB_WIDTH
from ..core.slab_graph import SlabGraph
from ..core.worklist import pool_edges
from ..kernels.slab_sweep.ops import slice_rows


def slab_contrib_sums_ref(keys: jnp.ndarray, valid: jnp.ndarray,
                          contrib: jnp.ndarray) -> jnp.ndarray:
    """Per-slab partial sums of contrib over valid lanes — pure-jnp oracle for
    the ``slab_pagerank`` kernel.  keys (S,128) uint32, valid (S,128) bool,
    contrib (V,) f32 → (S,) f32."""
    idx = jnp.where(valid, keys.astype(jnp.int32), 0)
    vals = jnp.where(valid, contrib[idx], 0.0)
    return jnp.sum(vals, axis=1)


@partial(jax.jit, static_argnames=("damping", "max_iter", "contrib_impl",
                                   "rows"))
def pagerank(g_in: SlabGraph, out_degree: jnp.ndarray, *,
             init_pr: Optional[jnp.ndarray] = None,
             damping: float = 0.85, error_margin: float = 1e-5,
             max_iter: int = 100,
             contrib_impl: str = "ref",
             rows: Optional[int] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static (init_pr=None) or dynamic (init_pr=warm start) PageRank.

    Returns (pagerank vector, iterations).  ``contrib_impl`` selects the pool
    sweep implementation: "ref" is the in-module jnp oracle; "sweep" (alias
    "pallas") is the shared slab-sweep engine's sum semiring — the kernel
    under ``kernels/slab_sweep`` of which the historical ``slab_pagerank``
    kernel is the specialization.  ``rows`` (static) bounds the sweep to the
    allocated slab prefix (``GraphStore.sweep_rows``; bit-identical).
    """
    g_in = slice_rows(g_in, rows)
    n = g_in.n_vertices
    view = pool_edges(g_in)
    seg = jnp.where(g_in.slab_vertex >= 0, g_in.slab_vertex, n)

    if contrib_impl in ("pallas", "sweep"):
        from ..kernels.slab_sweep.ops import sweep_partials

        def _sums(keys, valid, contrib):
            return sweep_partials(g_in, contrib, semiring="sum")
    elif contrib_impl == "ref":
        _sums = slab_contrib_sums_ref
    else:
        raise ValueError(f"unknown contrib_impl {contrib_impl!r}")

    pr0 = (jnp.full((n,), 1.0 / n, jnp.float32) if init_pr is None
           else init_pr.astype(jnp.float32))
    zero_out = out_degree == 0
    has_sink = jnp.any(zero_out)

    def super_step(pr):
        contrib = jnp.where(out_degree > 0,
                            pr / jnp.maximum(out_degree, 1).astype(jnp.float32),
                            0.0)
        partial_sums = _sums(view.dst, view.valid, contrib)
        sums = jax.ops.segment_sum(partial_sums, seg, num_segments=n + 1)[:n]
        new_pr = (1.0 - damping) / n + damping * sums
        teleport = jnp.sum(jnp.where(zero_out, pr, 0.0)) / n
        new_pr = jnp.where(has_sink, new_pr + damping * teleport, new_pr)
        return new_pr

    def cond(carry):
        _, delta, it = carry
        return (delta > error_margin) & (it < max_iter)

    def body(carry):
        pr, _, it = carry
        new_pr = super_step(pr)
        delta = jnp.sum(jnp.abs(new_pr - pr))
        return new_pr, delta, it + 1

    pr, _, iters = jax.lax.while_loop(
        cond, body, (pr0, jnp.asarray(jnp.inf, jnp.float32),
                     jnp.asarray(0, jnp.int32)))
    return pr, iters


def pagerank_dynamic(g_in: SlabGraph, out_degree: jnp.ndarray,
                     prev_pr: jnp.ndarray, **kw):
    """Incremental/decremental PageRank — warm start (paper: 'the same
    static-PageRank algorithm is applied on the entire graph after performing
    insertion/deletion', seeded with the pre-update vector)."""
    return pagerank(g_in, out_degree, init_pr=prev_pr, **kw)


# ---------------------------------------------------------------------------
# repro.stream registration hook
# ---------------------------------------------------------------------------

def stream_property(*, damping: float = 0.85, error_margin: float = 1e-5,
                    max_iter: int = 100, contrib_impl: str = "ref"):
    """PropertySpec for the stream registry: PageRank over the store's
    transpose view with device-resident out-degrees; incremental ==
    decremental == warm start, so ``on_batch`` ignores the batch contents."""
    from .. import obs
    from ..stream.properties import PropertySpec

    def _run(store, init_pr=None):
        if store.transpose is None:
            raise ValueError("pagerank stream property sweeps the transpose "
                             "view; build the store with with_transpose=True")
        with obs.span("pagerank.solve", warm=init_pr is not None) as sp:
            pr, iters = pagerank(store.transpose, store.out_degree,
                                 init_pr=init_pr, damping=damping,
                                 error_margin=error_margin,
                                 max_iter=max_iter,
                                 contrib_impl=contrib_impl,
                                 rows=store.sweep_rows())
            obs.count("pagerank.iters", iters)
            sp.sync_on(pr)
        return pr

    return PropertySpec(
        name="pagerank",
        init=lambda store: _run(store),
        on_batch=lambda store, state, batch: _run(store, init_pr=state),
        refresh=lambda store: _run(store),
        state_like=lambda n_vertices: jnp.zeros((n_vertices,), jnp.float32),
        collapse_replay=True)  # warm start only reads the current graph

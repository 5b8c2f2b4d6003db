"""Union-Find for (incremental) weakly connected components.

The paper (§3.3.1, §4.4) uses UNION-ASYNC hooking with full path compression.
On TPU, lock-free CAS hooking becomes a *batch* union: repeatedly hook the
larger root under the smaller via a min-scatter (deterministic resolution of
concurrent unions), then pointer-jump (full path compression as vectorised
pointer doubling) until every vertex points at its root.  Each round is a
handful of gathers/scatters over (V,) arrays — ideal VPU work — and converges
in O(log V) rounds.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


def init_parents(n: int) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.int32)


def compress(parent: jnp.ndarray) -> jnp.ndarray:
    """Full path compression via pointer doubling: parent <- parent[parent]
    until fixpoint.  O(log depth) gathers."""
    def cond(p):
        return jnp.any(p != p[p])

    def body(p):
        return p[p]

    return jax.lax.while_loop(cond, body, parent)


def find(parent: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Roots for a batch of vertices; assumes ``parent`` is compressed."""
    return parent[v]


@jax.jit
def union_batch_counted(parent: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                        mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """UNION-ASYNC over an edge batch: hook max-root under min-root until no
    edge connects two distinct roots.  Deterministic: conflicting hooks on a
    root resolve by scatter-min.  Returns the parents and the number of
    hooking rounds (an int32 scalar)."""
    parent = compress(parent)

    def cond(state):
        parent, active, _ = state
        return jnp.any(active)

    def body(state):
        parent, active, rounds = state
        ru = parent[jnp.where(mask, u, 0)]
        rv = parent[jnp.where(mask, v, 0)]
        differs = mask & (ru != rv) & active
        hi = jnp.maximum(ru, rv)
        lo = jnp.minimum(ru, rv)
        tgt = jnp.where(differs, hi, parent.shape[0])  # OOB drop
        parent = parent.at[tgt].min(lo, mode="drop")
        parent = compress(parent)
        ru2 = parent[jnp.where(mask, u, 0)]
        rv2 = parent[jnp.where(mask, v, 0)]
        return parent, mask & (ru2 != rv2), rounds + 1

    active0 = mask & (parent[jnp.where(mask, u, 0)] !=
                      parent[jnp.where(mask, v, 0)])
    parent, _, rounds = jax.lax.while_loop(
        cond, body, (parent, active0, jnp.zeros((), jnp.int32)))
    return parent, rounds


def union_batch(parent: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                mask: jnp.ndarray) -> jnp.ndarray:
    """``union_batch_counted`` without the round count."""
    return union_batch_counted(parent, u, v, mask)[0]


def component_labels(parent: jnp.ndarray) -> jnp.ndarray:
    """Representative (min-id root) per vertex after compression."""
    return compress(parent)


def count_components(parent: jnp.ndarray) -> jnp.ndarray:
    p = compress(parent)
    return jnp.sum((p == jnp.arange(p.shape[0], dtype=p.dtype))
                   .astype(jnp.int32))

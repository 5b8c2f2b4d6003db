"""Jit'd wrapper for the slab_pagerank pool sweep (sum-semiring
specialization of ``kernels/slab_sweep`` — see that package for the generic
frontier-masked engine).  Signature is adapted to the algorithm layer's
(keys, valid, contrib) convention.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import resolve_impl
from .kernel import slab_contrib_sums_pallas
from .ref import slab_contrib_sums_ref


def slab_contrib_sums(keys: jnp.ndarray, valid: jnp.ndarray,
                      contrib: jnp.ndarray, *,
                      impl: str = "auto") -> jnp.ndarray:
    """(S,128) keys + (S,128) valid mask + (V,) contrib → (S,) partials.

    Both engines re-derive the lane mask from sentinels; a row is treated
    as allocated iff any lane of ``valid`` is set, matching the algorithm
    layer's PoolView.  ``impl``: ``"auto"`` = ``"ref"``, or ``"pallas"``.
    """
    impl, interpret = resolve_impl(impl, None, xla="ref",
                                   impls=("pallas", "ref"))
    n_vertices = contrib.shape[0]
    owner = jnp.where(jnp.any(valid, axis=1), 0, -1).astype(jnp.int32)
    if impl == "ref":
        return slab_contrib_sums_ref(keys, owner, contrib,
                                     n_vertices=n_vertices)
    return slab_contrib_sums_pallas(keys, owner, contrib,
                                    n_vertices=n_vertices,
                                    interpret=interpret)


__all__ = ["slab_contrib_sums", "slab_contrib_sums_pallas",
           "slab_contrib_sums_ref"]

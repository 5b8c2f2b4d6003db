"""Slab kernel families: the graph path's compute layers.

Each subpackage ships: kernel.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit'd dispatch wrapper over the XLA engine and the Pallas kernel),
ref.py (pure-jnp oracle).  The Pallas kernels are validated in
``interpret=True`` mode against their oracle (tests/test_kernels.py and the
per-family suites).

``impl="auto"`` resolves to each family's XLA engine on every backend
(``resolve_impl``), so the path the CPU tests run is the path the TPU runs.
The TPU v5e compiler refuses every Pallas kernel of the graph path today:
the probe, sweep and intersect kernels gather with a dynamic integer index
(``Cannot do int indexing on TPU``), the commit kernel loads from a ref
outside VMEM/SMEM, and the compaction kernels use ``cumsum`` and
``dynamic_slice``, which Mosaic does not lower.  A kernel returns under
``auto`` only once it compiles and beats the XLA engine on the chip.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple


def resolve_impl(impl: str, interpret: Optional[bool], *, xla: str,
                 impls: Sequence[str]) -> Tuple[str, bool]:
    """``(impl, interpret)`` for one dispatch: ``"auto"`` becomes the
    family's XLA engine ``xla``; an explicit ``"pallas"`` runs interpreted
    off-TPU and compiled (so refused, today) on a TPU."""
    import jax

    if impl == "auto":
        impl = xla
    if impl not in impls:
        raise ValueError(f"unknown impl {impl!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return impl, interpret

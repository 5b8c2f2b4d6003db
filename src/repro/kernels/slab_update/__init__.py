"""Fused slab-update engine: the batched insert/delete/query plane.

The update-side sibling of ``slab_sweep``: a tiled Pallas chain-walk probe
with per-tile termination, fused placement/tombstone commit, run-local
O(batch) planning, and buffer-donating in-place mutation — see DESIGN.md §6
for the API contract and when the ``ref.py`` oracle path is the right
choice.
"""
from .ops import (FORWARD, IMPLS, SYMMETRIC, TRANSPOSE, apply_update,
                  delete_edges, insert_edges, query_edges,
                  slab_commit_pallas, slab_probe_pallas, update_views)
from .ref import (ProbeCount, batch_valid, delete_edges_ref,
                  insert_edges_ref, probe, probe_counted, query_edges_ref)

__all__ = ["IMPLS", "FORWARD", "TRANSPOSE", "SYMMETRIC",
           "apply_update", "delete_edges", "insert_edges", "query_edges",
           "update_views", "slab_probe_pallas", "slab_commit_pallas",
           "batch_valid", "delete_edges_ref", "insert_edges_ref",
           "query_edges_ref", "probe", "probe_counted", "ProbeCount"]

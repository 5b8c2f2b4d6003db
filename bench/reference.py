"""Plain references the benchmark holds the served answers to.

Straightforward numpy over the live edge list, importing nothing of the
program.  ``ref_pagerank``, ``ref_components`` and ``partition_of`` are
copied from ``chip_smoke.py``; ``replay_live`` is the set semantics of an
update stream (deletes before inserts within an epoch, the last operation
on a pair wins), written out independently of the generator's ledger.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np


def keys_of(src, dst) -> np.ndarray:
    """(src, dst) pairs as ``src << 32 | dst`` uint64 keys."""
    return ((np.asarray(src).astype(np.uint64) << np.uint64(32))
            | np.asarray(dst).astype(np.uint64))


def split_keys(keys) -> Tuple[np.ndarray, np.ndarray]:
    keys = np.asarray(keys, np.uint64)
    return ((keys >> np.uint64(32)).astype(np.uint32),
            (keys & np.uint64(0xFFFF_FFFF)).astype(np.uint32))


def arc_keys(edge_keys: np.ndarray, directed: bool) -> np.ndarray:
    """Sorted keys of the arcs that hold the sorted ``edge_keys``: an
    undirected edge ``(u, v)``, ``u < v``, is the arcs ``(u, v)`` and
    ``(v, u)``."""
    if directed:
        return edge_keys
    s, d = split_keys(edge_keys)
    return np.sort(np.concatenate([edge_keys, keys_of(d, s)]))


def replay_live(initial: np.ndarray,
                epochs: Sequence[Tuple[np.ndarray, np.ndarray]]
                ) -> np.ndarray:
    """Sorted live keys after applying ``epochs`` to the sorted, distinct
    ``initial`` keys.  Each epoch is ``(deleted_keys, inserted_keys)``;
    within an epoch deletes come first, and across the whole stream the
    last operation on a key decides whether it is live."""
    if not epochs:
        return initial
    keys = np.concatenate([k for d, i in epochs for k in (d, i)])
    order = np.concatenate([np.full(len(k), 2 * e + j, np.int64)
                            for e, (d, i) in enumerate(epochs)
                            for j, k in enumerate((d, i))])
    idx = np.lexsort((order, keys))
    k, op = keys[idx], order[idx] % 2
    last = np.ones(len(k), bool)
    last[:-1] = k[1:] != k[:-1]
    k, inserted = k[last], op[last] == 1
    removed, added = k[~inserted], k[inserted]
    pos = np.minimum(np.searchsorted(removed, initial), max(len(removed) - 1,
                                                            0))
    gone = (removed[pos] == initial) if len(removed) else \
        np.zeros(len(initial), bool)
    return np.union1d(initial[~gone], added)


def ref_components(n: int, src, dst) -> np.ndarray:
    """Smallest vertex id of each vertex's weak component: min-label
    propagation over both edge directions, with pointer jumping."""
    s = src.astype(np.int64)
    d = dst.astype(np.int64)
    label = np.arange(n, dtype=np.int64)
    while True:
        m = np.minimum(label[s], label[d])
        new = label.copy()
        np.minimum.at(new, s, m)
        np.minimum.at(new, d, m)
        new = new[new]
        if np.array_equal(new, label):
            return label
        label = new


def ref_pagerank(n: int, src, dst, *, damping: float, tol: float = 1e-10,
                 max_iter: int = 1000) -> np.ndarray:
    """Float64 power iteration: teleport (1-d)/n, dangling mass spread
    uniformly -- the semantics the PageRank property computes."""
    out = np.bincount(src, minlength=n).astype(np.float64)
    sink = out == 0
    inv = np.where(sink, 0.0, 1.0 / np.maximum(out, 1.0))
    pr = np.full(n, 1.0 / n)
    for _ in range(max_iter):
        sums = np.bincount(dst, weights=(pr * inv)[src], minlength=n)
        new = (1.0 - damping) / n + damping * (sums + pr[sink].sum() / n)
        delta = np.abs(new - pr).sum()
        pr = new
        if delta < tol:
            break
    return pr


def partition_of(labels) -> np.ndarray:
    """Canonical form of a partition: each vertex's smallest class member."""
    labels = np.asarray(labels, np.int64)
    first = np.full(labels.max() + 1, len(labels), np.int64)
    np.minimum.at(first, labels, np.arange(len(labels)))
    return first[labels]

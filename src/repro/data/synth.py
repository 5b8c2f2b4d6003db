"""Synthetic data pipelines: RMAT edge streams, LM token batches, recsys
interaction streams.  Deterministic per seed; host-side numpy generation
(the container's 'storage layer'), device feeding via the loop.
"""
from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np


def rmat_edges(n_vertices: int, n_edges: int, *, seed: int = 0,
               a: float = 0.57, b: float = 0.19, c: float = 0.19
               ) -> Tuple[np.ndarray, np.ndarray]:
    """R-MAT power-law edge generator (Graph500-style)."""
    rng = np.random.default_rng(seed)
    scale = int(np.ceil(np.log2(max(n_vertices, 2))))
    src = np.zeros(n_edges, dtype=np.int64)
    dst = np.zeros(n_edges, dtype=np.int64)
    # one quadrant per level: src bit from r, dst bit from r2 conditioned
    # on it (in place: Graph500 scales are tens of millions of edges)
    for level in range(scale):
        src_bit = rng.random(n_edges) >= a + b
        r2 = rng.random(n_edges)
        dst_bit = np.where(src_bit, r2 >= c / (c + (1 - a - b - c) + 1e-12),
                           r2 >= a / (a + b))
        src <<= 1
        src |= src_bit
        dst <<= 1
        dst |= dst_bit
    src %= n_vertices
    dst %= n_vertices
    keep = src != dst
    return src[keep].astype(np.uint32), dst[keep].astype(np.uint32)


def uniform_edges(n_vertices: int, n_edges: int, *, seed: int = 0,
                  weighted: bool = False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_vertices, n_edges).astype(np.uint32)
    dst = rng.integers(0, n_vertices, n_edges).astype(np.uint32)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if weighted:
        return src, dst, rng.uniform(0.1, 10.0, len(src)).astype(np.float32)
    return src, dst


def edge_batches(src: np.ndarray, dst: np.ndarray, batch_size: int,
                 *, pad_to: Optional[int] = None
                 ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Padded fixed-shape batches (mask in third position)."""
    cap = pad_to or batch_size
    for i in range(0, len(src), batch_size):
        s = src[i:i + batch_size]
        d = dst[i:i + batch_size]
        ps = np.full(cap, 0xFFFFFFFF, np.uint32)
        pd = np.full(cap, 0xFFFFFFFF, np.uint32)
        ps[:len(s)] = s
        pd[:len(d)] = d
        yield ps, pd, np.arange(cap) < len(s)


def lm_batches(vocab_size: int, batch: int, seq_len: int, *,
               seed: int = 0) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Synthetic next-token data: Zipf-ish tokens; labels = shift-by-one."""
    rng = np.random.default_rng(seed)
    while True:
        z = rng.zipf(1.3, size=(batch, seq_len + 1)) % vocab_size
        toks = z[:, :-1].astype(np.int32)
        labels = z[:, 1:].astype(np.int32)
        yield toks, labels


def recsys_batches(n_items: int, batch: int, hist_len: int, *,
                   seed: int = 0):
    rng = np.random.default_rng(seed)
    while True:
        hist = (rng.zipf(1.2, size=(batch, hist_len)) % n_items) \
            .astype(np.int32)
        lens = rng.integers(1, hist_len + 1, batch)
        mask = (np.arange(hist_len)[None] < lens[:, None]) \
            .astype(np.float32)
        target = (rng.zipf(1.2, size=batch) % n_items).astype(np.int32)
        yield hist, mask, target

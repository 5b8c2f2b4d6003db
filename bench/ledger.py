"""The generator's exact set of live edges.

Copied from ``repro.launch.serve`` (``EdgeLedger``, ``edge_keys``) so that a
change to the program cannot move the benchmark's yardstick: deletes are
sampled from here, so they always hit live edges, and the expected answer of
every membership read is read from here at the moment the read is drawn.
"""
from __future__ import annotations

import numpy as np


_EMPTY = np.uint64(0xFFFF_FFFF_FFFF_FFFF)   # never a key: src < 2^32 - 1
_DEAD = np.uint64(0xFFFF_FFFF_FFFF_FFFE)    # removed; probing passes it


def edge_keys(src, dst) -> np.ndarray:
    """(src, dst) pairs as ``src << 32 | dst`` uint64 keys."""
    return ((np.asarray(src).astype(np.uint64) << np.uint64(32))
            | np.asarray(dst).astype(np.uint64))


class EdgeLedger:
    """The workload generator's exact set of live edges (not graph state:
    the store owns the graph), with per-request work bounded by the batch.

    Keys live in a dense array, so deletes sample uniform indices and
    remove by swapping tail entries into the holes.  A linear-probing hash
    set of the same keys answers "already present?" for inserts.  Removed
    slots stay as markers that probes pass and inserts reuse; the set is
    rebuilt from the live keys, doubling as needed, once live keys plus
    markers would fill half of it.  ``capacity`` pre-sizes both for that
    many live edges.
    """

    def __init__(self, src, dst, *, capacity: int = 0):
        self._keys = np.empty(max(int(capacity), len(src), 1), np.uint64)
        self._n = 0
        self._rehash()
        self.add(src, dst)

    def __len__(self) -> int:
        return self._n

    def edges(self):
        """(src, dst) uint32 copies of the live edges."""
        return _split(self._keys[:self._n])

    def _rehash(self) -> None:
        bits = max(2 * len(self._keys) - 1, 1).bit_length()
        self._table = np.full(1 << bits, _EMPTY, np.uint64)
        self._shift = np.uint64(64 - bits)
        self._used = 0                       # bound on non-empty slots
        self._place(self._keys[:self._n])

    def _home(self, keys):
        # Fibonacci hashing: the top bits of key * 2^64/phi
        return ((keys * np.uint64(0x9E37_79B9_7F4A_7C15)) >> self._shift
                ).astype(np.int64)

    def _slots(self, keys) -> np.ndarray:
        """Table slot of each key, -1 where absent."""
        mask = len(self._table) - 1
        pos = self._home(keys)
        out = np.full(len(keys), -1, np.int64)
        todo = np.arange(len(keys))
        while todo.size:
            t = self._table[pos[todo]]
            hit = t == keys[todo]
            out[todo[hit]] = pos[todo[hit]]
            todo = todo[~hit & (t != _EMPTY)]
            pos[todo] = (pos[todo] + 1) & mask
        return out

    def _place(self, keys) -> None:
        """Put distinct absent ``keys`` into the hash set."""
        mask = len(self._table) - 1
        pos = self._home(keys)
        todo = np.arange(len(keys))
        while todo.size:
            p = pos[todo]
            free = np.isin(self._table[p], (_EMPTY, _DEAD))
            # every claimant writes, one write per slot survives: the keys
            # read back are the round's winners
            self._table[p[free]] = keys[todo[free]]
            won = free & (self._table[p] == keys[todo])
            todo = todo[~won]
            pos[todo] = (pos[todo] + 1) & mask
        self._used += len(keys)

    def contains(self, src, dst) -> np.ndarray:
        return self._slots(edge_keys(src, dst)) >= 0

    def add(self, src, dst) -> int:
        """Insert-set semantics: adds the pairs not already live; returns
        how many were added."""
        keys = np.unique(edge_keys(src, dst))
        keys = keys[self._slots(keys) < 0]
        n = self._n + len(keys)
        if n > len(self._keys):
            grown = np.empty(max(n, 2 * len(self._keys)), np.uint64)
            grown[:self._n] = self._keys[:self._n]
            self._keys = grown
            self._rehash()
        elif 2 * (self._used + len(keys)) > len(self._table):
            self._rehash()
        self._place(keys)
        self._keys[self._n:n] = keys
        self._n = n
        return len(keys)

    def sample(self, k: int, rng):
        """Up to ``k`` distinct live edges, uniformly, as (src, dst)."""
        idx = rng.choice(self._n, min(k, self._n), replace=False)
        return _split(self._keys[idx])

    def take(self, k: int, rng):
        """``sample`` and remove: the deletes of one update request."""
        idx = np.sort(rng.choice(self._n, min(k, self._n), replace=False))
        keys = self._keys[idx]
        self._table[self._slots(keys)] = _DEAD
        tail = self._n - len(idx)
        holes = idx[idx < tail]
        movers = np.setdiff1d(np.arange(tail, self._n), idx,
                              assume_unique=True)
        self._keys[holes] = self._keys[movers]
        self._n = tail
        return _split(keys)


def _split(keys):
    return ((keys >> np.uint64(32)).astype(np.uint32),
            (keys & np.uint64(0xFFFF_FFFF)).astype(np.uint32))

"""The benchmark's one general generator.

It makes the graph of a configuration (``bench/configs/<name>.json``) and
the request rounds of a traffic mix (``bench/traffic/<name>.json``) from
the run's seed and the configuration's own numbers, and from nothing else.

Graphs, as the GAP Benchmark Suite builds its ``kron`` and ``urand`` inputs:

* ``kronecker``: Graph500 R-MAT, ``edgefactor * 2**scale`` pairs, each bit
  level picking a quadrant with probabilities A/B/C/D (the arithmetic of
  ``repro.data.synth.rmat_edges``), then vertex labels permuted at random as
  the Graph500 specification asks;
* ``uniform``: Erdos-Renyi, ``edgefactor * 2**scale`` uniform pairs.

Both drop self-loops and duplicate pairs.  With ``"directed": false`` (as
GAP builds both graphs) a pair is an undirected edge ``{u, v}``: it is kept
as ``(min, max)``, so ``(u, v)`` and ``(v, u)`` are one edge, and the store
holds it as the two arcs ``(u, v)`` and ``(v, u)``; every insert and delete
carries both arcs.  The pairs are drawn on the device in one jitted call,
and inserts during the run are drawn from the same distribution (the same
permutation), one call per update step.

A configuration with a ``graph_seed`` has one graph, drawn from that seed
(edges and label permutation), whatever the run's seed, as GAP's builder
draws each of its graphs from one fixed seed; the run's seed then draws
the inserts and everything sampled from the ledger.

A traffic mix is a list of steps that make up one round:

* ``{"kind": "update", "deletes": D, "inserts": I}``: ``D`` live edges
  sampled from the ledger, and ``I`` pairs drawn from the graph's own
  distribution (self-loops dropped), as one ``UpdateBatch``: one epoch;
* ``{"kind": "member", "pairs": P, "repeat": R, "mix": {...}}``: ``R``
  membership reads of ``P`` pairs each; ``mix`` gives the shares of edges
  deleted (``just_deleted``) or newly inserted (``just_inserted``) by the
  last update, of ``live`` edges and of ``random`` pairs; an undirected
  edge is asked for in a direction drawn at random;
* ``{"kind": "property", "name": N}``: a read of the analytic ``N``.

Rounds are drawn in order, so round ``r`` is the same whatever number of
rounds is drawn.  Each round keeps what the reference needs: the expected
count of each update (in arcs, as the store counts them) and the expected
answer of each membership read, both read off the ledger when the round is
drawn, and the edge keys each update deleted and inserted.  The ledger and
the keys hold edges: for an undirected graph, ``(min, max)``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .ledger import EdgeLedger
from .reference import keys_of, split_keys

GENERATORS = ("kronecker", "uniform")


def _device_key(ss: np.random.SeedSequence):
    return jax.random.wrap_key_data(
        jnp.asarray(ss.generate_state(2, np.uint32)), impl="threefry2x32")


def arcs(src, dst, directed: bool):
    """The arcs the store holds for these edges: both directions of each
    undirected edge."""
    if directed:
        return src, dst
    return np.concatenate([src, dst]), np.concatenate([dst, src])


def canonical(src, dst, directed: bool):
    """An edge's key pair: ``(min, max)`` for an undirected graph."""
    if directed:
        return src, dst
    return np.minimum(src, dst), np.maximum(src, dst)


def _draw(key, perm, *, kind: str, n: int, scale: int, abc):
    if kind == "kronecker":
        a, b, c = abc

        def level(i, carry):
            s, d = carry
            r = jax.random.uniform(jax.random.fold_in(key, i), (2, n))
            sb = r[0] >= a + b
            db = jnp.where(sb, r[1] >= c / (1.0 - a - b), r[1] >= a / (a + b))
            return ((s << 1) | sb.astype(jnp.uint32),
                    (d << 1) | db.astype(jnp.uint32))

        z = jnp.zeros(n, jnp.uint32)
        s, d = jax.lax.fori_loop(0, scale, level, (z, z))
    elif kind == "uniform":
        r = jax.random.randint(key, (2, n), 0, 1 << scale, jnp.int32)
        s, d = r[0].astype(jnp.uint32), r[1].astype(jnp.uint32)
    else:
        raise ValueError(f"unknown generator {kind!r}; known: {GENERATORS}")
    if perm is not None:
        s, d = perm[s], perm[d]
    return s, d


_STATIC = ("kind", "n", "scale", "abc")
_pairs = jax.jit(_draw, static_argnames=_STATIC)


@partial(jax.jit, static_argnames=_STATIC + ("directed",))
def _distinct(key, perm, *, kind, n, scale, abc, directed):
    """Edges sorted by (src, dst), ``(min, max)`` where undirected, and a
    mask of the first copy of each edge that is not a self-loop."""
    s, d = _draw(key, perm, kind=kind, n=n, scale=scale, abc=abc)
    if not directed:
        s, d = jnp.minimum(s, d), jnp.maximum(s, d)
    s, d = jax.lax.sort((s, d), num_keys=2)
    first = jnp.concatenate([jnp.ones(1, bool),
                             (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    return s, d, first & (s != d)


class Graph:
    """A configuration's graph and its insert distribution, from a seed
    (the graph from the configuration's ``graph_seed`` where it has one)."""

    def __init__(self, config: dict, seed: int):
        self.kind = config["generator"]
        self.scale = int(config["scale"])
        self.n_vertices = 1 << self.scale
        self.n_generated = int(config["edgefactor"]) << self.scale
        self.directed = bool(config["directed"])
        #: arcs the store holds per edge
        self.arcs_per_edge = 1 if self.directed else 2
        self.abc = tuple(float(config[k]) for k in ("a", "b", "c")) \
            if self.kind == "kronecker" else None
        graph_ss, perm_ss, ins_ss, host_ss = \
            np.random.SeedSequence(seed).spawn(4)
        if config.get("graph_seed") is not None:
            # one fixed graph, as GAP's builder draws it from a fixed seed:
            # the run's seed draws the updates and reads
            graph_ss, perm_ss = np.random.SeedSequence(
                int(config["graph_seed"])).spawn(2)
        self._graph_key = _device_key(graph_ss)
        self._ins_key = _device_key(ins_ss)
        self._perm = (jax.random.permutation(
            _device_key(perm_ss), self.n_vertices).astype(jnp.uint32)
            if config.get("permute_labels") else None)
        #: the host generator of everything drawn from the ledger
        self.rng = np.random.default_rng(host_ss)

    def _kw(self, n: int) -> dict:
        return dict(kind=self.kind, n=n, scale=self.scale, abc=self.abc)

    def edges(self):
        """The initial edges: distinct, no self-loops, sorted by (src, dst),
        ``(min, max)`` where undirected."""
        s, d, keep = jax.device_get(_distinct(
            self._graph_key, self._perm, directed=self.directed,
            **self._kw(self.n_generated)))
        return s[keep], d[keep]

    def pairs(self, index: int, n: int):
        """``n`` edges of the graph's distribution for update step
        ``index``, self-loops dropped, ``(min, max)`` where undirected."""
        key = jax.random.fold_in(self._ins_key, index)
        s, d = jax.device_get(_pairs(key, self._perm, **self._kw(n)))
        keep = s != d
        return canonical(s[keep], d[keep], self.directed)


class Rounds:
    """The request rounds of one traffic mix over one graph, drawn in
    order and kept, with what the reference needs to check them."""

    def __init__(self, graph: Graph, traffic: dict, src, dst):
        self.graph = graph
        self.steps = traffic["round"]
        self.ledger = EdgeLedger(src, dst, capacity=len(src) + (1 << 16))
        #: sorted distinct edge keys of the initial graph (the replay's
        #: start)
        self.initial_keys = keys_of(src, dst)
        self.rounds: List[Dict] = []

    def draw(self, k: int) -> None:
        for _ in range(k):
            self.rounds.append(self._round(len(self.rounds)))

    def _round(self, index: int) -> Dict:
        g, ledger, rng = self.graph, self.ledger, self.graph.rng
        requests, epochs = [], []
        # the edges the last update deleted and newly inserted
        deleted = added = np.zeros(0, np.uint64)
        for n_step, step in enumerate(self.steps):
            kind = step["kind"]
            if kind == "update":
                del_s, del_d = ledger.take(int(step["deletes"]), rng)
                n_ins = int(step["inserts"])
                ins_s, ins_d = g.pairs(index * len(self.steps) + n_step,
                                       n_ins) if n_ins else (del_s[:0],) * 2
                ins_keys = np.unique(keys_of(ins_s, ins_d))
                new = ins_keys[~ledger.contains(*split_keys(ins_keys))]
                n_added = ledger.add(ins_s, ins_d)
                assert n_added == len(new)
                deleted, added = np.sort(keys_of(del_s, del_d)), new
                epochs.append((deleted, ins_keys))
                ins_s, ins_d = arcs(ins_s, ins_d, g.directed)
                del_s, del_d = arcs(del_s, del_d, g.directed)
                requests.append({
                    "kind": "update",
                    "request": dict(ins_src=ins_s, ins_dst=ins_d,
                                    del_src=del_s, del_dst=del_d),
                    "expect": {"inserted": g.arcs_per_edge * len(new),
                               "deleted": len(del_s)}})
            elif kind == "member":
                for _ in range(int(step.get("repeat", 1))):
                    requests.append(self._member(step, deleted, added, rng))
            elif kind == "property":
                requests.append({"kind": "property", "name": step["name"]})
            else:
                raise ValueError(f"unknown traffic step {kind!r}")
        return {"index": index, "requests": requests, "epochs": epochs}

    def _member(self, step: dict, deleted, added, rng) -> Dict:
        """One membership read; ``deleted``/``added`` are the edge keys the
        last update removed and newly added."""
        n = int(step["pairs"])
        mix = step["mix"]
        parts = []
        for share, keys in (("just_deleted", deleted),
                            ("just_inserted", added)):
            k = min(int(n * mix.get(share, 0.0)), len(keys))
            if k:
                parts.append(split_keys(rng.choice(keys, k, replace=False)))
        parts.append(self.ledger.sample(int(n * mix.get("live", 0.0)), rng))
        k_rand = n - sum(len(s) for s, _ in parts)
        rand = rng.integers(0, self.graph.n_vertices, (k_rand, 2))
        parts.append((rand[:, 0].astype(np.uint32),
                      rand[:, 1].astype(np.uint32)))
        src = np.concatenate([s for s, _ in parts])
        dst = np.concatenate([d for _, d in parts])
        if not self.graph.directed:           # ask in either direction
            flip = rng.random(n) < 0.5
            src, dst = np.where(flip, dst, src), np.where(flip, src, dst)
        edge = canonical(src, dst, self.graph.directed)
        expect = self.ledger.contains(*edge)
        # what a read one epoch stale would answer: the control of the
        # guarantee that an acknowledged update is visible to the next read
        keys = keys_of(*edge)
        stale = expect.copy()
        stale[_member_of(keys, added)] = False
        stale[_member_of(keys, deleted)] = True
        return {"kind": "member", "request": (src, dst), "expect": expect,
                "stale": stale}


def _member_of(keys: np.ndarray, sorted_keys: np.ndarray) -> np.ndarray:
    """``np.isin`` against a sorted, distinct key array."""
    if not len(sorted_keys):
        return np.zeros(len(keys), bool)
    pos = np.minimum(np.searchsorted(sorted_keys, keys), len(sorted_keys) - 1)
    return sorted_keys[pos] == keys

"""The generator, the copied ledger and the references: deterministic from
the seed, and equal to plain Python sets where they can be."""
from __future__ import annotations

import json

import numpy as np
import pytest

from benchkit import ROOT, TINY_TRAFFIC


def _config(real: str, scale: int = 9) -> dict:
    config = json.loads((ROOT / "bench" / "configs" / f"{real}.json")
                        .read_text())
    config["scale"] = scale
    return config


@pytest.mark.parametrize("real", ["kron-s21", "urand-s18"])
def test_graph_is_distinct_loop_free_and_fixed_by_the_seed(real):
    from bench.generator import Graph
    a = Graph(_config(real), 2 ** 40 + 3)
    s, d = a.edges()
    keys = (s.astype(np.uint64) << 32) | d
    assert np.all(s != d) and np.all(np.diff(keys.astype(np.int64)) > 0)
    assert not a.directed and np.all(s < d)     # GAP's graphs: undirected
    assert a.n_generated // 2 < len(s) <= a.n_generated
    s2, d2 = Graph(_config(real), 2 ** 40 + 3).edges()
    assert np.array_equal(s, s2) and np.array_equal(d, d2)
    # the configuration's graph_seed fixes the graph for every run seed,
    # and seeds that differ only above 32 bits draw different inserts
    s3, d3 = Graph(_config(real), 3).edges()
    assert np.array_equal(s, s3) and np.array_equal(d, d3)
    assert not np.array_equal(a.pairs(0, 64)[0], Graph(_config(real), 3)
                              .pairs(0, 64)[0])
    # without one, such seeds give different graphs
    free = {k: v for k, v in _config(real).items() if k != "graph_seed"}
    s4, _ = Graph(free, 2 ** 40 + 3).edges()
    s5, _ = Graph(free, 3).edges()
    assert len(s5) != len(s4) or not np.array_equal(s5, s4)


def test_kronecker_labels_are_permuted():
    from bench.generator import Graph
    for permute, hub_at_zero in ((False, True), (True, False)):
        config = dict(_config("kron-s21", 12), permute_labels=permute)
        s, d = Graph(config, 5).edges()
        deg = np.bincount(np.concatenate([s, d]), minlength=1 << 12)
        assert (int(np.argmax(deg)) == 0) == hub_at_zero


def _python_rounds(traffic, seed, n_rounds, directed):
    from bench.generator import Graph, Rounds
    g = Graph(dict(_config("urand-s18"), directed=directed), seed)
    s, d = g.edges()
    rounds = Rounds(g, traffic, s, d)
    rounds.draw(n_rounds)
    return set(zip(s.tolist(), d.tolist())), rounds


@pytest.mark.parametrize("directed", [False, True])
def test_rounds_expect_what_a_python_set_says(directed):
    def edge(p):
        return p if directed else (min(p), max(p))

    live, rounds = _python_rounds(TINY_TRAFFIC["ingest-t"], 11, 4, directed)
    touched = before = set()
    for rnd in rounds.rounds:
        for item in rnd["requests"]:
            if item["kind"] == "update":
                r = item["request"]
                dels = set(zip(r["del_src"].tolist(), r["del_dst"].tolist()))
                ins = set(zip(r["ins_src"].tolist(), r["ins_dst"].tolist()))
                if not directed:         # every edge comes as both arcs
                    assert {(b, a) for a, b in dels | ins} == dels | ins
                dels, ins = {edge(p) for p in dels}, {edge(p) for p in ins}
                assert dels <= live
                before = set(live)
                live -= dels
                per = 1 if directed else 2
                assert item["expect"] == {"inserted": per * len(ins - live),
                                          "deleted": per * len(dels)}
                touched = dels | (ins - live)
                live |= ins
            else:
                pairs = list(zip(*(a.tolist() for a in item["request"])))
                assert item["expect"].tolist() == [edge(p) in live
                                                   for p in pairs]
                assert item["stale"].tolist() == [edge(p) in before
                                                  for p in pairs]
                # a quarter of the pairs are edges the last update touched
                assert sum(edge(p) in touched for p in pairs) \
                    >= len(pairs) // 4
                if not directed:         # asked for in both directions
                    assert any(a > b for a, b in pairs)
    # the same seed draws the same rounds, however many are drawn
    _, again = _python_rounds(TINY_TRAFFIC["ingest-t"], 11, 2, directed)
    for a, b in zip(again.rounds, rounds.rounds):
        assert np.array_equal(a["requests"][0]["request"]["ins_src"],
                              b["requests"][0]["request"]["ins_src"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_replay_live_is_the_set_semantics(seed):
    from bench.reference import arc_keys, keys_of, replay_live, split_keys
    rng = np.random.default_rng(seed)
    init = np.unique(rng.integers(0, 64, 200).astype(np.uint64))
    live = set(init.tolist())
    epochs = []
    for _ in range(6):
        d = np.unique(rng.integers(0, 64, 20).astype(np.uint64))
        i = np.unique(rng.integers(0, 64, 20).astype(np.uint64))
        epochs.append((d, i))
        live = (live - set(d.tolist())) | set(i.tolist())
    got = replay_live(init, epochs)
    assert got.tolist() == sorted(live)
    s, d = split_keys(keys_of(np.array([3, 7], np.uint32),
                              np.array([9, 1], np.uint32)))
    assert s.tolist() == [3, 7] and d.tolist() == [9, 1]
    both = arc_keys(keys_of(np.array([1, 2], np.uint32),
                            np.array([5, 3], np.uint32)), directed=False)
    assert list(zip(*(x.tolist() for x in split_keys(both)))) == \
        [(1, 5), (2, 3), (3, 2), (5, 1)]


def test_ledger_copy_behaves_as_the_programs():
    from bench.ledger import EdgeLedger
    from repro.launch.serve import EdgeLedger as Original
    rng = np.random.default_rng(4)
    src, dst = rng.integers(0, 300, (2, 2000)).astype(np.uint32)
    a, b = EdgeLedger(src, dst), Original(src, dst)
    r1, r2 = np.random.default_rng(9), np.random.default_rng(9)
    for _ in range(5):
        assert [x.tolist() for x in a.take(100, r1)] == \
            [x.tolist() for x in b.take(100, r2)]
        ins = rng.integers(0, 300, (2, 150)).astype(np.uint32)
        assert a.add(*ins) == b.add(*ins)
        q = rng.integers(0, 300, (2, 500)).astype(np.uint32)
        assert np.array_equal(a.contains(*q), b.contains(*q))
    assert sorted(zip(*(x.tolist() for x in a.edges()))) == \
        sorted(zip(*(x.tolist() for x in b.edges())))


@pytest.mark.parametrize("q", [50, 95, 99])
def test_percentile_is_the_histograms(q):
    from bench.stats import percentile
    from repro.obs.metrics import Histogram
    samples = np.random.default_rng(q).exponential(0.01, 301).tolist()
    h = Histogram()
    for x in samples:
        h.record(x)
    assert percentile(samples, q) == h.percentile(q)


def test_open_loop_arithmetic():
    from bench.stats import due_times, latency_from_due
    due = due_times(10.0, 4, 2.0)
    assert due == [10.0, 10.5, 11.0, 11.5]
    assert latency_from_due(due[3], 12.0) == 0.5


def test_references_on_a_small_graph():
    from bench.reference import partition_of, ref_components, ref_pagerank
    src = np.array([0, 1, 2, 4, 5], np.uint32)
    dst = np.array([1, 2, 0, 5, 4], np.uint32)
    comp = ref_components(7, src, dst)
    assert comp.tolist() == [0, 0, 0, 3, 4, 4, 6]
    assert partition_of([5, 5, 2, 2, 9]).tolist() == [0, 0, 2, 2, 4]
    pr = ref_pagerank(7, src, dst, damping=0.85)
    assert pr.sum() == pytest.approx(1.0)
    # a dense power iteration with the same teleport and dangling rule
    n, d = 7, 0.85
    out = np.bincount(src, minlength=n)
    m = np.zeros((n, n))
    for s, t in zip(src, dst):
        m[t, s] += 1.0 / out[s]
    p = np.full(n, 1.0 / n)
    for _ in range(500):
        p = (1 - d) / n + d * (m @ p + p[out == 0].sum() / n)
    assert np.abs(p - pr).sum() < 1e-8

"""pagerank_iters: mean power iterations of a PageRank solve
(``pagerank.iters`` counter of the ``pagerank.solve`` spans)."""
from bench.counters import mean


def read(run):
    return mean(run, "pagerank.solve", "pagerank.iters")

"""member_query_ms: mean time of the store's membership reads
(``store.query`` spans: host padding, the probe loop and the read-back of
the answer), from ``obs`` spans."""
from bench.counters import mean_ms


def read(run):
    return mean_ms(run, "store.query")

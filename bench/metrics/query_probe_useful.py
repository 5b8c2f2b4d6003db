"""query_probe_useful: share of the rows that the membership probe
gathered for a query still walking its chain, in percent: 100 x visits
over hops x width, summed over the ``store.query`` spans' probe counters.
The loop runs until the batch's longest chain ends."""
from bench.counters import probe_useful


def read(run):
    return probe_useful(run, "store.query")

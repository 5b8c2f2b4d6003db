"""query_rows_useful: share of the rows that the membership probe
gathered for a query still walking its chain, in percent: 100 x visits
over rows, summed over the ``store.query`` spans' probe counters."""
from bench.probe_rows import rows_useful


def read(run):
    return rows_useful(run, "store.query")

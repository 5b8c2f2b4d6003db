"""update_rows_useful: share of the rows that the update engine's probe
loops gathered for a query still walking its chain, in percent: 100 x
visits over rows, summed over the ``store.apply.dispatch`` spans' probe
counters (every view's delete, insert and reverse-query loops)."""
from bench.probe_rows import rows_useful


def read(run):
    return rows_useful(run, "store.apply.dispatch")

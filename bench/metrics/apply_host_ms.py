"""apply_host_ms: mean host time of the store's apply phases per epoch
(``store.apply.host_dedup`` + ``capacity`` + ``notify``), from ``obs``
spans."""
from bench.spans import children_seconds


def read(run):
    times = children_seconds(run.spans, "store.apply",
                             ("store.apply.host_dedup",
                              "store.apply.capacity", "store.apply.notify"))
    return 1e3 * sum(times) / len(times) if times else None

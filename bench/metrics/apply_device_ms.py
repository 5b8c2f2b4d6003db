"""apply_device_ms: mean time of the store's engine phases per epoch
(``store.apply.dispatch``, which ends in reading the applied counts, and
``store.apply.epoch_close``, which syncs on the views), from ``obs``
spans."""
from bench.spans import children_seconds


def read(run):
    times = children_seconds(run.spans, "store.apply",
                             ("store.apply.dispatch",
                              "store.apply.epoch_close"))
    return 1e3 * sum(times) / len(times) if times else None

"""update_probe_useful: the same share over every probe loop of the update
engine (forward/transpose x delete/insert, and a symmetric view's), from
the ``store.apply.dispatch`` spans' probe counters, in percent."""
from bench.counters import probe_useful


def read(run):
    return probe_useful(run, "store.apply.dispatch")

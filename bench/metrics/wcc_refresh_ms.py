"""wcc_refresh_ms: mean time of WCC's static recompute after a deleting
epoch (``wcc.refresh`` spans, synced on the labels), from ``obs`` spans."""
from bench.counters import mean_ms


def read(run):
    return mean_ms(run, "wcc.refresh")

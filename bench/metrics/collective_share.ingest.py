"""collective_share.ingest: share of the device's busy time spent in
collective operations (all-to-all, all-gather, all-reduce, ...), in
percent: 100 x ``collective_s`` over ``busy_s`` of ``bench.trace_reduce``,
both averaged over the chips.  None on one chip, where nothing is
exchanged."""


def read(run):
    if run.device is None or run.device["devices"] < 2 \
            or not run.device["busy_s"]:
        return None
    return 100.0 * run.device["collective_s"] / run.device["busy_s"]

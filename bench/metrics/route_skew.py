"""route_skew: how unevenly a sharded store's epochs route their batches
over the shards: S x the sum over epochs of the busiest shard's share over
the sum of the routed totals, for inserts and deletes together.  1.0 is an
even spread; S means one shard took every edge.  Read off the program's
``store.route.<ins|del>.shard<k>`` counters (the forward view's owners),
kept after each update request of a traced window."""
import re

_SHARD = re.compile(r"^store\.route\.(ins|del)\.shard(\d+)$")


def read(run):
    seen = [q["counters"] for q in run.requests("update") if "counters" in q]
    peak = total = 0
    shards = 0
    before = {}
    for now in seen:
        per = {}
        for key, v in now.items():
            m = _SHARD.match(key)
            if m:
                per.setdefault(m.group(1), []).append(v - before.get(key, 0))
        for counts in per.values():
            shards = max(shards, len(counts))
            peak += max(counts)
            total += sum(counts)
        before = now
    return shards * peak / total if total else None

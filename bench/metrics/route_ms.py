"""route_ms: mean time per epoch of a sharded store's routing phase
(``store.apply.route``: sizing each owner shard's bucket from the batch on
the host, the routing caps, and any growth of the shards' pools), from
``obs`` spans."""
from bench.spans import children_seconds


def read(run):
    times = children_seconds(run.spans, "store.apply", ("store.apply.route",))
    return 1e3 * sum(times) / len(times) if times else None

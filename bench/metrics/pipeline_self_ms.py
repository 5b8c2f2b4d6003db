"""pipeline_self_ms: mean self time of the request plane per update request
(``pipeline.update`` spans less their child spans: coalescing, request
bookkeeping, and the store's maintenance check), from ``obs`` spans."""
from bench.spans import self_seconds


def read(run):
    times = self_seconds(run.spans, "pipeline.update")
    return 1e3 * sum(times) / len(times) if times else None

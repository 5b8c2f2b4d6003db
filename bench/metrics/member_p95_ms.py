"""member_p95_ms: 95th percentile of every membership read of the window,
each timed from submission to the answer in host memory."""
from bench.stats import percentile


def read(run):
    times = [q["seconds"] for q in run.requests("member")]
    return 1e3 * percentile(times, 95) if times else None

"""pagerank_read_ms: mean time of the window's PageRank reads, each timed
from submission until the device has finished the vector."""


def read(run):
    times = [q["seconds"] for q in run.requests("property", "pagerank")]
    return 1e3 * sum(times) / len(times) if times else None

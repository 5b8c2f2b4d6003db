"""wcc_read_ms: mean time of the window's component reads, each timed
from submission until the device has finished the labels."""


def read(run):
    times = [q["seconds"] for q in run.requests("property", "wcc")]
    return 1e3 * sum(times) / len(times) if times else None

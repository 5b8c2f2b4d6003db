"""ingest_edges_per_s: edges the store reported as inserted plus deleted,
over the whole window (host clock, the window closed by a device sync)."""


def read(run):
    edges = sum(r["edges"] for r in run.rounds)
    return edges / run.window_s if edges else None

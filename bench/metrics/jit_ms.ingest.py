"""jit_ms.ingest: milliseconds per round of the compile stages that JAX
reported in the window (tracing, lowering, backend compile or cache load:
``jit.*_s`` counters on the program's spans); 0.0 where none fired."""
from bench.counters import jit_ms_per_round


def read(run):
    return jit_ms_per_round(run)

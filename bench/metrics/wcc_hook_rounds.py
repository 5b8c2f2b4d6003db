"""wcc_hook_rounds: mean hooking rounds of the union in a WCC refresh
(``wcc.hook_rounds`` counter of the ``wcc.refresh`` spans)."""
from bench.counters import mean


def read(run):
    return mean(run, "wcc.refresh", "wcc.hook_rounds")

"""The share of the slab rows a probe loop gathered that belonged to a
query still walking its chain, from the ``probe.<loop>.{visits,rows}``
span counters.  ``rows`` counts what the loops gathered as they ran, so
this share rises as finished lanes leave a loop; a program that counts no
``rows`` reads as None."""
from __future__ import annotations

from typing import Optional

from bench.counters import of


def rows_useful(run, name: str) -> Optional[float]:
    """100 x the visits over the rows gathered, summed over every probe
    loop of the ``name`` spans."""
    visits = rows = 0
    for c in of(run, name) or ():
        for key, v in c.items():
            if key.startswith("probe.") and key.endswith(".rows"):
                rows += v
                visits += c[key[:-len("rows")] + "visits"]
    return 100.0 * visits / rows if rows else None

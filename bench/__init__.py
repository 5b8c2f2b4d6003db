"""The on-chip benchmark of the dynamic-graph service (see ``BENCHMARK.json``
and ``PERF.md``).  ``python3 -m bench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell once."""

"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` scales graphs up;
the default 'quick' profile keeps the whole suite CPU-friendly.  The paper's
claims are *ratios* (vs baseline / vs static recompute); absolute times on
this CPU container are not comparable with the paper's RTX 2080 Ti.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every suite's structured rows "
                         "(timing.take_rows) as one JSON artifact")
    ap.add_argument("--check", action="store_true",
                    help="perf-regression gate: snapshot the committed "
                         "BENCH_*.json baselines before the suites "
                         "overwrite them, diff the fresh artifacts after "
                         "(benchmarks.regress), exit 1 on regression")
    args = ap.parse_args()
    scale = "full" if args.full else "quick"

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (chaos_bench, churn_bench, dynamic_speedup, memory_table,
                   pagerank_bench, serve_bench, sharded_bench, sweep_bench,
                   traversal, triangle_bench, update_bench,
                   update_throughput, wcc_bench)
    suites = {
        "memory_table": memory_table,        # Table 5
        "update_throughput": update_throughput,  # Figs 3–5
        "traversal": traversal,              # Fig 6
        "dynamic_speedup": dynamic_speedup,  # Fig 7
        "pagerank": pagerank_bench,          # Figs 8–10
        "triangle": triangle_bench,          # Fig 11
        "wcc": wcc_bench,                    # Fig 12 + Table 6
        "sweep": sweep_bench,                # old-path vs slab-sweep engine
        "serve": serve_bench,                # legacy loop vs repro.stream
        "update": update_bench,              # Fig 5 old-path vs update engine
        "sharded": sharded_bench,            # 8-device sharded stream plane
        "churn": churn_bench,                # maintenance plane under churn
        "chaos": chaos_bench,                # fault injection + WAL recovery
    }
    from . import timing
    only = set(args.only.split(",")) if args.only else None
    baselines = None
    if args.check:
        # MUST snapshot before any suite runs: each suite overwrites its
        # committed artifact in place
        from . import regress
        baselines = regress.snapshot_baselines(only)
    print("name,us_per_call,derived")
    failed = []
    rows = {}
    timing.take_rows()                       # drop any import-time strays
    for name, mod in suites.items():
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            mod.run(scale)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        rows[name] = timing.take_rows()
    if args.json:
        import json
        with open(args.json, "w") as f:
            json.dump({"scale": scale, "suites": rows}, f, indent=2)
        print(f"# structured rows -> {args.json}")
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    if baselines is not None:
        from . import regress
        ran = [n for n in suites if not only or n in only]
        if not regress.report(regress.check(baselines, ran)):
            print("# PERF REGRESSION — see regress FAIL rows above")
            sys.exit(1)
    print("# all benchmark suites completed")


if __name__ == "__main__":
    main()

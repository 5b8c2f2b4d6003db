"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 -m bench.run --workload kron-s21.ingest --seed 7 --seconds 10 \\
        --trace 0

Set-up (generation from the seed, store build, analytics init, warm-up of
every shape the cell uses) runs first, then the window measures for
``--seconds``, then every answer is checked against the plain reference.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit,
which also end standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits with status 2 and prints no result.

JAX's compilation cache is kept in ``.bench_cache/jax`` of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    sys.path.insert(0, str(ROOT / "src"))       # the system under test
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    bench.peaks(devices[0].device_kind)        # an unknown chip is an error
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace),
                              devices=devices[:cell["chips"]],
                              t_start=T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

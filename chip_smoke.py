"""Bring-up smoke test: the served dynamic-graph path on the TPU.

Drives the path a user runs -- ``repro.launch.serve.build_service``: store
-> ``PropertyRegistry`` -> ``RequestPipeline`` -- once at Graph500 size, and
checks every answer against a plain numpy reference over the live edge set.

* default: one chip, a ``GraphStore`` (forward + transpose views) over a
  Graph500 R-MAT graph (edgefactor 16, A/B/C = 0.57/0.19/0.19,
  deduplicated) of scale 21;
* ``--chips 4``: a 4-shard ``ShardedGraphStore`` on a ``("shard",)`` mesh
  of four chips, at scale 22.  It runs only that path and its reference.

The stream: 6 update batches of 65,536 edges (25% of them deletes of live
edges), a 4,096-pair membership read after each, then PageRank, BFS from
vertex 0 and WCC reads.  Membership, BFS levels and the WCC partition must
equal the reference exactly; PageRank must be within an L1 distance of
``PAGERANK_L1_TOL`` of a float64 power iteration.  An error response, a
shed update or a stale read fails the run, as does a miscounted update.

The earlier lines report the device, bytes per view, peak device memory,
compile against steady seconds per request class, and each check.  The
last line is ``{"ok": true, "device": {...}}`` when every check passed.
Without a TPU the script exits with status 2 and prints no result;
``--allow-cpu`` runs it on the CPU for rehearsal (and the tests), and the
last line then names the cpu platform.

    python chip_smoke.py                 # one chip, scale 21
    python chip_smoke.py --chips 4       # four chips, scale 22
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import numpy as np

BATCHES = 6
DELETE_FRAC = 0.25
MEMBER_PAIRS = 4096
EDGEFACTOR = 16
DAMPING = 0.85
# PageRank stops once an iteration moves the vector by at most 1e-5 in L1.
# At damping 0.85 that bounds its distance to the fixpoint by
# 1e-5 * 0.85 / 0.15 = 5.7e-5; the remaining 4.3e-5 covers float32 rounding
# of the per-vertex sums, which the chip accumulates in another order.
PAGERANK_L1_TOL = 1e-4
# XLA's own compile time (tracing and lowering nest and overlap, so they are
# left out rather than double counted)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# plain numpy references over the live edge list
# ---------------------------------------------------------------------------

def ref_bfs_levels(n: int, src, dst, root: int = 0) -> np.ndarray:
    """Level-synchronous BFS over a CSR of the live edges; -1 = unreached."""
    order = np.argsort(src, kind="stable")
    nbr = dst[order].astype(np.int64)
    start = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=start[1:])
    level = np.full(n, -1, np.int64)
    level[root] = 0
    frontier = np.array([root], np.int64)
    depth = 0
    while frontier.size:
        lo, cnt = start[frontier], start[frontier + 1] - start[frontier]
        pos = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt) \
            + np.arange(cnt.sum())
        reached = np.unique(nbr[pos])
        frontier = reached[level[reached] < 0]
        depth += 1
        level[frontier] = depth
    return level


def ref_components(n: int, src, dst) -> np.ndarray:
    """Smallest vertex id of each vertex's weak component: min-label
    propagation over both edge directions, with pointer jumping."""
    s = src.astype(np.int64)
    d = dst.astype(np.int64)
    label = np.arange(n, dtype=np.int64)
    while True:
        m = np.minimum(label[s], label[d])
        new = label.copy()
        np.minimum.at(new, s, m)
        np.minimum.at(new, d, m)
        new = new[new]
        if np.array_equal(new, label):
            return label
        label = new


def ref_pagerank(n: int, src, dst, *, tol: float = 1e-10,
                 max_iter: int = 1000) -> np.ndarray:
    """Float64 power iteration: teleport (1-d)/n, dangling mass spread
    uniformly -- the semantics the PageRank property computes."""
    out = np.bincount(src, minlength=n).astype(np.float64)
    sink = out == 0
    inv = np.where(sink, 0.0, 1.0 / np.maximum(out, 1.0))
    pr = np.full(n, 1.0 / n)
    for _ in range(max_iter):
        sums = np.bincount(dst, weights=(pr * inv)[src], minlength=n)
        new = (1.0 - DAMPING) / n + DAMPING * (sums + pr[sink].sum() / n)
        delta = np.abs(new - pr).sum()
        pr = new
        if delta < tol:
            break
    return pr


def partition_of(labels) -> np.ndarray:
    """Canonical form of a partition: each vertex's smallest class member."""
    labels = np.asarray(labels, np.int64)
    first = np.full(labels.max() + 1, len(labels), np.int64)
    np.minimum.at(first, labels, np.arange(len(labels)))
    return first[labels]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=None,
                    help="Graph500 scale (default 21, or 22 with --chips 4)")
    ap.add_argument("--batch", type=int, default=65536,
                    help="edges per update batch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on the CPU backend (rehearsal and tests)")
    args = ap.parse_args(argv)
    scale = args.scale or (21 if args.chips == 1 else 22)

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()[:args.chips]
    dev = devices[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2

    print(f"[smoke] compile cache {cache}")
    failures = _run(args, scale, devices)
    for f in failures:
        print(f"[smoke] FAILED: {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


def _run(args, scale: int, devices) -> list:
    """The whole smoke run; returns the failed checks."""
    import jax

    from repro.kernels.slab_compact.ops import _resolve as compact_impl
    from repro.kernels.slab_intersect.ops import _resolve as intersect_impl
    from repro.kernels.slab_sweep.ops import _resolve as sweep_impl
    from repro.kernels.slab_update.ops import _resolve as update_impl

    def say(msg: str) -> None:
        print(f"[smoke] {msg}", flush=True)

    dev = devices[0]
    say(f"device {dev.device_kind} platform={dev.platform} "
        f"count={len(devices)}")
    say("impl='auto' -> " + " ".join(
        f"{name}={fn('auto', None)[0]}" for name, fn in (
            ("slab_sweep", sweep_impl), ("slab_update", update_impl),
            ("slab_compact", compact_impl),
            ("slab_intersect", intersect_impl))))

    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == _COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _stream(args, scale, devices, say, compile_s)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _stream(args, scale: int, devices, say, compile_s) -> list:
    import jax

    from repro.data.synth import rmat_edges
    from repro.launch.serve import EdgeLedger, build_service, update_request
    from repro.stream import MembershipQuery, PropertyRead, dedup_pairs

    failures = []

    def check(what: str, ok: bool, detail: str) -> None:
        say(f"check {what:22s} {'ok  ' if ok else 'FAIL'} {detail}")
        if not ok:
            failures.append(f"{what}: {detail}")

    # -- data: Graph500 R-MAT from the seed, deduplicated ------------------
    V = 1 << scale
    t0 = time.perf_counter()
    src, dst = rmat_edges(V, EDGEFACTOR * V, seed=args.seed)
    src, dst, _ = dedup_pairs(src, dst)
    budget = BATCHES * args.batch
    ledger = EdgeLedger(src, dst, capacity=len(src) + budget)
    rng = np.random.default_rng(args.seed)
    say(f"graph500 scale {scale}: V={V} E={len(src)} (R-MAT A/B/C="
        f"0.57/0.19/0.19, edgefactor {EDGEFACTOR}, deduplicated) in "
        f"{time.perf_counter() - t0:.1f}s host")

    # -- the served path, as serve builds it -------------------------------
    t0, c0 = time.perf_counter(), compile_s[0]
    store, registry, pipeline = build_service(
        V, src, dst, shards=args.chips, insert_budget=budget)
    jax.block_until_ready(registry.states())
    say(f"boot: {type(store).__name__} shards={args.chips} built with "
        f"properties in {time.perf_counter() - t0:.1f}s "
        f"(XLA compile {compile_s[0] - c0:.1f}s)")
    del src, dst

    timings = {}

    def serve(cls, req):
        t, c = time.perf_counter(), compile_s[0]
        resp = pipeline.run([req])[0]
        jax.block_until_ready(resp.payload)
        wall, comp = time.perf_counter() - t, compile_s[0] - c
        timings.setdefault(cls, []).append((wall, comp))
        say(f"{cls} {wall:.3f}s (XLA compile {comp:.3f}s)")
        ok = resp.kind != "error" and not resp.payload.get("stale", False)
        check(f"{cls} response", ok and resp.version == store.version,
              f"kind={resp.kind} version={resp.version}"
              + ("" if ok else f" payload={resp.payload}"))
        return resp

    # -- 6 mixed update epochs, a membership read after each ---------------
    for b in range(BATCHES):
        n0 = len(ledger)
        req = update_request(ledger, rng, V, args.batch, DELETE_FRAC)
        n_del = len(req.del_src)
        want = {"inserted": len(ledger) - n0 + n_del, "deleted": n_del}
        resp = serve("update", req)
        got = {k: resp.payload.get(k) for k in want}
        check(f"update {b}", got == want, f"store {got} reference {want}")

        k_del = min(n_del, MEMBER_PAIRS // 4)
        live_s, live_d = ledger.sample(MEMBER_PAIRS // 2, rng)
        k_rand = MEMBER_PAIRS - k_del - len(live_s)
        rand = rng.integers(0, V, (k_rand, 2)).astype(np.uint32)
        qs = np.concatenate([req.del_src[:k_del], live_s, rand[:, 0]])
        qd = np.concatenate([req.del_dst[:k_del], live_d, rand[:, 1]])
        resp = serve("member", MembershipQuery(src=qs, dst=qd))
        found = np.asarray(resp.payload.get("found", ()))
        want_found = ledger.contains(qs, qd)
        check(f"membership {b}", np.array_equal(found, want_found),
              f"{int(want_found.sum())}/{len(qs)} present, "
              f"{int((found != want_found).sum())} disagree")

    # -- analytics reads vs numpy over the live edge set -------------------
    t0 = time.perf_counter()
    src, dst = ledger.edges()
    value = {name: serve(name, PropertyRead(name)).payload.get("value")
             for name in ("pagerank", "bfs_0", "wcc")}
    t_ref = time.perf_counter()
    pr = np.asarray(value["pagerank"], np.float64)
    pr_ref = ref_pagerank(V, src, dst)
    l1 = float(np.abs(pr - pr_ref).sum())
    check("pagerank", bool(np.isfinite(pr).all()) and l1 <= PAGERANK_L1_TOL,
          f"L1={l1:.3e} (tol {PAGERANK_L1_TOL:g}) "
          f"max={float(np.abs(pr - pr_ref).max()):.3e}")
    bfs = value["bfs_0"]
    lv = np.asarray(getattr(bfs, "dist", bfs))
    reach = lv < 2 ** 30            # f32 INF=1e30 / i32 UNREACHED=2^30
    lv_ref = ref_bfs_levels(V, src, dst)
    ok = np.array_equal(reach, lv_ref >= 0) and np.array_equal(
        lv[reach].astype(np.int64), lv_ref[reach])
    check("bfs levels", ok, f"reachable={int(reach.sum())} "
          f"reference={int((lv_ref >= 0).sum())} depth={int(lv_ref.max())}")
    comp = partition_of(value["wcc"])
    comp_ref = ref_components(V, src, dst)
    check("wcc partition", np.array_equal(comp, comp_ref),
          f"components={int((comp == np.arange(V)).sum())} "
          f"reference={int((comp_ref == np.arange(V)).sum())}")
    say(f"host references took {time.perf_counter() - t_ref:.1f}s "
        f"(reads {t_ref - t0:.1f}s)")

    # -- where the state lives and what it costs ---------------------------
    for name, view in store.views.items():
        leaves = jax.tree.leaves(view)
        on = sorted({d.id for x in leaves for d in x.devices()})
        say(f"view {name:9s} {sum(int(x.nbytes) for x in leaves)} bytes "
            f"on devices {on}, sweeps cover {store.sweep_rows(name)} of "
            f"{getattr(view, 'graphs', view).keys.shape[-2]} slab rows")
        if args.chips > 1:
            check(f"{name} placement", len(on) == args.chips,
                  f"pools on {len(on)} distinct devices")
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    for cls, runs in timings.items():
        walls = [w for w, _ in runs]
        steady = (f"steady median {statistics.median(walls[1:]):.3f}s over "
                  f"{len(walls) - 1}" if len(walls) > 1 else
                  "steady not measured (one call)")
        say(f"time {cls:9s} calls={len(runs)} first {walls[0]:.3f}s "
            f"(XLA compile {sum(c for _, c in runs):.3f}s), {steady}")
    return failures


if __name__ == "__main__":
    sys.exit(main())

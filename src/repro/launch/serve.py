"""Serving launcher — the paper's kind of serving: a streaming dynamic-graph
analytics service, now a thin driver over the `repro.stream` subsystem.

The request stream mixes batched edge updates (inserts AND deletes — the
paper benchmarks both directions) with analytics queries (PageRank / BFS /
WCC / membership).  All state lives in the subsystem: the ``GraphStore``
keeps the forward/transposed/symmetric views consistent and closes every
update epoch via ``update_slab_pointers``; out-degrees are the store's
device-resident ``degree`` field (no host-side ``np.add.at`` shadow); the
``PropertyRegistry`` maintains each analytic incrementally under the chosen
policy, and the ``RequestPipeline`` coalesces update bursts and batches
membership queries.  With ``--maintain`` (default) a ``MaintenancePolicy``
rides the store's epoch close: tombstone-heavy pools compact and shrink
instead of inflating forever, which is what keeps a long-running serving
process memory- and latency-stable under churn (DESIGN.md §8).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


_EMPTY = np.uint64(0xFFFF_FFFF_FFFF_FFFF)   # never a key: src < 2^32 - 1
_DEAD = np.uint64(0xFFFF_FFFF_FFFF_FFFE)    # removed; probing passes it


def edge_keys(src, dst) -> np.ndarray:
    """(src, dst) pairs as ``src << 32 | dst`` uint64 keys."""
    return ((np.asarray(src).astype(np.uint64) << np.uint64(32))
            | np.asarray(dst).astype(np.uint64))


class EdgeLedger:
    """The workload generator's exact set of live edges (not graph state:
    the store owns the graph), with per-request work bounded by the batch.

    Keys live in a dense array, so deletes sample uniform indices and
    remove by swapping tail entries into the holes.  A linear-probing hash
    set of the same keys answers "already present?" for inserts.  Removed
    slots stay as markers that probes pass and inserts reuse; the set is
    rebuilt from the live keys, doubling as needed, once live keys plus
    markers would fill half of it.  ``capacity`` pre-sizes both for that
    many live edges.
    """

    def __init__(self, src, dst, *, capacity: int = 0):
        self._keys = np.empty(max(int(capacity), len(src), 1), np.uint64)
        self._n = 0
        self._rehash()
        self.add(src, dst)

    def __len__(self) -> int:
        return self._n

    def edges(self):
        """(src, dst) uint32 copies of the live edges."""
        return _split(self._keys[:self._n])

    def _rehash(self) -> None:
        bits = max(2 * len(self._keys) - 1, 1).bit_length()
        self._table = np.full(1 << bits, _EMPTY, np.uint64)
        self._shift = np.uint64(64 - bits)
        self._used = 0                       # bound on non-empty slots
        self._place(self._keys[:self._n])

    def _home(self, keys):
        # Fibonacci hashing: the top bits of key * 2^64/phi
        return ((keys * np.uint64(0x9E37_79B9_7F4A_7C15)) >> self._shift
                ).astype(np.int64)

    def _slots(self, keys) -> np.ndarray:
        """Table slot of each key, -1 where absent."""
        mask = len(self._table) - 1
        pos = self._home(keys)
        out = np.full(len(keys), -1, np.int64)
        todo = np.arange(len(keys))
        while todo.size:
            t = self._table[pos[todo]]
            hit = t == keys[todo]
            out[todo[hit]] = pos[todo[hit]]
            todo = todo[~hit & (t != _EMPTY)]
            pos[todo] = (pos[todo] + 1) & mask
        return out

    def _place(self, keys) -> None:
        """Put distinct absent ``keys`` into the hash set."""
        mask = len(self._table) - 1
        pos = self._home(keys)
        todo = np.arange(len(keys))
        while todo.size:
            p = pos[todo]
            free = np.isin(self._table[p], (_EMPTY, _DEAD))
            # every claimant writes, one write per slot survives: the keys
            # read back are the round's winners
            self._table[p[free]] = keys[todo[free]]
            won = free & (self._table[p] == keys[todo])
            todo = todo[~won]
            pos[todo] = (pos[todo] + 1) & mask
        self._used += len(keys)

    def contains(self, src, dst) -> np.ndarray:
        return self._slots(edge_keys(src, dst)) >= 0

    def add(self, src, dst) -> int:
        """Insert-set semantics: adds the pairs not already live; returns
        how many were added."""
        keys = np.unique(edge_keys(src, dst))
        keys = keys[self._slots(keys) < 0]
        n = self._n + len(keys)
        if n > len(self._keys):
            grown = np.empty(max(n, 2 * len(self._keys)), np.uint64)
            grown[:self._n] = self._keys[:self._n]
            self._keys = grown
            self._rehash()
        elif 2 * (self._used + len(keys)) > len(self._table):
            self._rehash()
        self._place(keys)
        self._keys[self._n:n] = keys
        self._n = n
        return len(keys)

    def sample(self, k: int, rng):
        """Up to ``k`` distinct live edges, uniformly, as (src, dst)."""
        idx = rng.choice(self._n, min(k, self._n), replace=False)
        return _split(self._keys[idx])

    def take(self, k: int, rng):
        """``sample`` and remove: the deletes of one update request."""
        idx = np.sort(rng.choice(self._n, min(k, self._n), replace=False))
        keys = self._keys[idx]
        self._table[self._slots(keys)] = _DEAD
        tail = self._n - len(idx)
        holes = idx[idx < tail]
        movers = np.setdiff1d(np.arange(tail, self._n), idx,
                              assume_unique=True)
        self._keys[holes] = self._keys[movers]
        self._n = tail
        return _split(keys)


def _split(keys):
    return ((keys >> np.uint64(32)).astype(np.uint32),
            (keys & np.uint64(0xFFFF_FFFF)).astype(np.uint32))


def update_request(ledger: EdgeLedger, rng, n_vertices: int, batch: int,
                   delete_frac: float):
    """One mixed ``UpdateBatch``: ``batch * delete_frac`` deletes of live
    edges and uniform random inserts (self-loops dropped).  The ledger
    follows the store's semantics — deletes first, then inserts."""
    from ..stream import UpdateBatch

    n_del = int(batch * delete_frac)
    ins = rng.integers(0, n_vertices, (batch - n_del, 2)).astype(np.uint32)
    ins = ins[ins[:, 0] != ins[:, 1]]
    del_src, del_dst = ledger.take(n_del, rng)
    ledger.add(ins[:, 0], ins[:, 1])
    return UpdateBatch(ins_src=ins[:, 0], ins_dst=ins[:, 1],
                       del_src=del_src, del_dst=del_dst)


def build_requests(n_vertices, ledger: EdgeLedger, rng, *, n_requests: int,
                   batch: int, delete_frac: float, prop_names):
    """Synthesize the request mix, one generator step per served request.

    Deletions are sampled from ``ledger``, the generator's set of live
    edges, so the same generator drives sharded and unsharded stores.
    Yields (kind, request) pairs lazily so each update samples from the
    post-update ledger.
    """
    from ..stream import MembershipQuery, PropertyRead

    kinds = ["update"] + [f"read:{p}" for p in prop_names] + ["member"]
    for i in range(n_requests):
        kind = kinds[i % len(kinds)]
        if kind == "update":
            yield kind, update_request(ledger, rng, n_vertices, batch,
                                       delete_frac)
        elif kind.startswith("read:"):
            yield kind, PropertyRead(kind.split(":", 1)[1])
        else:
            q = rng.integers(0, n_vertices, (1024, 2)).astype(np.uint32)
            yield kind, MembershipQuery(src=q[:, 0], dst=q[:, 1])


def describe(resp, n_vertices: int) -> str:
    """One-line detail per response kind for the serve log."""
    p = resp.payload
    if resp.kind == "update":
        return f"inserted={p['inserted']} deleted={p['deleted']}"
    if resp.kind == "member":
        return f"hits={p['hits']}/{len(p['found'])}"
    if resp.kind == "property":
        v = np.asarray(p["value"].dist if hasattr(p["value"], "dist")
                       else p["value"])
        if p["name"].startswith("bfs"):
            # tree dist is f32 (INF=1e30), sharded levels are i32 (2^30)
            return f"reachable={int((v < 2 ** 30).sum())}"
        if p["name"] == "wcc":
            return f"components={int((v == np.arange(n_vertices)).sum())}"
        return f"top={float(v.max()):.5f}"
    return ""


def build_service(n_vertices: int, src, dst, *, shards: int = 1,
                  insert_budget: int, policy: str = "lazy",
                  maintain: bool = True, tombstone_ratio: float = 0.2,
                  health=None):
    """The served path, built once for ``serve`` and ``chip_smoke.py``:
    store → ``PropertyRegistry`` (PageRank, BFS from vertex 0, WCC under
    ``policy``) → ``RequestPipeline``.  Returns ``(store, registry,
    pipeline)``.

    ``src``/``dst`` is the deduped initial edge list and ``insert_budget``
    bounds how many edges the run inserts: it sizes the pool slack and the
    edge lists of BFS re-seeding and WCC unions, which must hold every live
    edge.  ``shards > 1`` builds a ``ShardedGraphStore`` placed on a
    ``("shard",)`` mesh of the first ``shards`` devices; with fewer
    devices it raises.  ``maintain`` attaches a ``MaintenancePolicy``
    (slab compaction + free-slab recycling at epoch close).
    """
    from ..algorithms import (bfs_stream_property, pagerank_stream_property,
                              wcc_stream_property)
    from ..distributed.sharded_graph import shard_mesh
    from ..stream import (GraphStore, MaintenancePolicy, PropertyRegistry,
                          RequestPipeline, ShardedGraphStore,
                          sharded_bfs_property, sharded_pagerank_property,
                          sharded_wcc_property)

    maintenance = (MaintenancePolicy(tombstone_ratio=tombstone_ratio)
                   if maintain else None)
    if shards > 1:
        # sharded serving plane: same views, vertex-partitioned, one shard
        # per device; the analytics run as distributed slab-sweep
        # super-steps
        store = ShardedGraphStore.from_edges(
            n_vertices, shards, src, dst, maintenance=maintenance,
            mesh=shard_mesh(shards))
        registry = PropertyRegistry(store)
        registry.register(sharded_pagerank_property(), policy=policy)
        registry.register(sharded_bfs_property(0), policy=policy)
        registry.register(sharded_wcc_property(), policy=policy)
    else:
        # pagerank/bfs/wcc read only the forward + transpose views; skip the
        # symmetric one rather than pay its maintenance every epoch
        store = GraphStore.from_edges(
            n_vertices, src, dst, hashing=False, with_symmetric=False,
            slack_slabs=insert_budget // 64 + 512, maintenance=maintenance)
        registry = PropertyRegistry(store)
        cap = len(src) + insert_budget + 4096
        registry.register(pagerank_stream_property(), policy=policy)
        registry.register(bfs_stream_property(0, edge_capacity=cap),
                          policy=policy)
        # the default cap (every pool lane) needs 13 GiB of temporaries at
        # Graph500 scale 21; the live edge bound needs 6
        registry.register(wcc_stream_property(cap=cap), policy=policy)
    pipeline = RequestPipeline(store, registry, health=health,
                               health_every=8)
    return store, registry, pipeline


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=20000)
    ap.add_argument("--initial-edges", type=int, default=100000)
    ap.add_argument("--requests", type=int, default=30)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--delete-frac", type=float, default=0.25,
                    help="fraction of each update batch that deletes")
    ap.add_argument("--policy", choices=["lazy", "eager"], default="lazy")
    ap.add_argument("--maintain", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="attach a MaintenancePolicy (slab compaction + "
                         "free-slab recycling at epoch close)")
    ap.add_argument("--tombstone-ratio", type=float, default=0.2,
                    help="compaction trigger: dead/occupied lanes")
    ap.add_argument("--shards", type=int, default=1,
                    help="vertex-partition the store across N shards, one "
                         "per device of a ('shard',) mesh (ShardedGraphStore; "
                         "N>1 needs N devices — on a CPU host "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--checkpoint", default=None,
                    help="directory to snapshot the store into at the end")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="arm the telemetry plane and write a Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing) on exit")
    ap.add_argument("--metrics", action="store_true",
                    help="arm the metrics registry and print the "
                         "counter/histogram table on exit")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="also export the metrics registry summary as JSON")
    ap.add_argument("--health", action="store_true",
                    help="run the SLO burn-rate HealthEngine inside the "
                         "pipeline and print live HealthReports")
    ap.add_argument("--slo-update-ms", type=float, default=2000.0,
                    help="--health: update-class latency SLO (objective "
                         "0.9; CPU-container default is deliberately "
                         "lenient)")
    ap.add_argument("--evidence-dir", default=None, metavar="DIR",
                    help="write a metrics + flight-recorder snapshot into "
                         "DIR on exit — atexit AND SIGTERM, so an "
                         "orchestrator kill still leaves evidence")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    from .. import obs
    if args.trace or args.metrics or args.metrics_json:
        # tracing and metrics arm together here: the trace export appends
        # the kernel counters as Perfetto counter tracks, and the metrics
        # table wants the span-adjacent histograms — both cost nothing
        # measurable next to the device work they time
        obs.enable()

    if args.evidence_dir:
        # the always-on flight recorder makes this worth wiring even
        # without --metrics: whatever kills this process, the ring's last
        # window and the metrics snapshot land on disk
        import atexit
        import json as _json
        import pathlib
        import signal
        import sys
        from ..obs import flight
        evdir = pathlib.Path(args.evidence_dir)
        _snapped = []

        def _snap_evidence():
            if _snapped:
                return                # idempotent: atexit + SIGTERM race
            _snapped.append(True)
            try:
                evdir.mkdir(parents=True, exist_ok=True)
                summary = obs.get_registry().summary()
                summary["kernels"] = obs.kernel_summary()
                (evdir / "metrics.json").write_text(
                    _json.dumps(summary, indent=2, default=str))
                flight.export_chrome_trace(evdir / "flight_trace.json")
                (evdir / "flight_events.json").write_text(_json.dumps(
                    {"stats": flight.stats(),
                     "events": flight.snapshot()}, indent=2))
                print(f"[serve] evidence snapshot -> {evdir}")
            except Exception as e:     # evidence must never mask the exit
                print(f"[serve] evidence snapshot failed: {e}")

        atexit.register(_snap_evidence)

        def _on_sigterm(signum, frame):
            # convert the kill into SystemExit so atexit (the snapshot
            # above) still runs before the process dies
            sys.exit(128 + signum)

        signal.signal(signal.SIGTERM, _on_sigterm)

    from ..data.synth import rmat_edges
    from ..stream import dedup_pairs

    rng = np.random.default_rng(args.seed)
    V = args.vertices
    src, dst = rmat_edges(V, args.initial_edges, seed=args.seed)
    src, dst, _ = dedup_pairs(src, dst)
    health = None
    if args.health:
        from ..obs.health import HealthEngine, SLOTarget
        slo_s = args.slo_update_ms / 1e3
        health = HealthEngine(
            [SLOTarget("update", latency_s=slo_s, objective=0.9),
             SLOTarget("property", latency_s=4 * slo_s, objective=0.9),
             SLOTarget("member", latency_s=slo_s, objective=0.9)],
            window=128)
    budget = args.requests * args.batch
    store, registry, pipeline = build_service(
        V, src, dst, shards=args.shards, insert_budget=budget,
        policy=args.policy, maintain=args.maintain,
        tombstone_ratio=args.tombstone_ratio, health=health)
    print(f"[serve] boot: V={V} E={store.n_edges} shards={args.shards}")

    # per-request-class latency histograms (standalone — always collected,
    # the flag-free Histogram class costs one record per request); the
    # update class is the apply path, everything else is query-side
    lat = {}
    t0 = time.time()
    ledger = EdgeLedger(src, dst, capacity=len(src) + budget)
    stream = build_requests(V, ledger, rng, n_requests=args.requests,
                            batch=args.batch, delete_frac=args.delete_frac,
                            prop_names=["pagerank", "bfs_0", "wcc"])
    for i, (kind, req) in enumerate(stream):
        resp = pipeline.run([req])[0]
        cls = "update" if resp.kind == "update" else resp.kind
        lat.setdefault(cls, obs.Histogram()).record(resp.latency_s)
        obs.observe(f"serve.latency.{cls}", resp.latency_s)
        print(f"[serve] req {i:03d} {kind:13s} {1e3 * resp.latency_s:8.1f}"
              f" ms  v{resp.version:<4d} {describe(resp, V)}")
        if health is not None and (i + 1) % 10 == 0:
            r = health.report()
            print(f"[serve] health: "
                  f"{'OK' if r.healthy else 'BURNING'} "
                  f"worst_burn={r.worst_burn:.2f} "
                  f"({r.worst_burn_class or '-'})")
    elapsed = time.time() - t0
    print(f"[serve] {args.requests} requests in {elapsed:.1f}s "
          f"({args.requests / elapsed:.2f} req/s), "
          f"store v{store.version}, E={store.n_edges}")
    # update-apply latency vs query latency, per class, exact percentiles
    for cls in ("update", "member", "property", "neighbors"):
        h = lat.get(cls)
        if h is None:
            continue
        s = h.summary()
        side = "apply" if cls == "update" else "query"
        print(f"[serve] latency {cls:9s} ({side}): n={s['count']:<4d} "
              f"mean={1e3 * s['mean_s']:8.1f} p50={1e3 * s['p50_s']:8.1f} "
              f"p95={1e3 * s['p95_s']:8.1f} p99={1e3 * s['p99_s']:8.1f} ms")
    st = store.pool_stats()
    print(f"[serve] pool: capacity={st['capacity_slabs']} slabs "
          f"(next_free={st['next_free']} free_top={st['free_top']}) "
          f"live={st['live_lanes']} tombstones={st['tombstone_lanes']} "
          f"(ratio {st['tombstone_ratio']:.3f}) "
          f"occupancy={st['occupancy']:.3f} "
          f"chains mean={st['mean_chain']:.2f} max={st['max_chain']}")
    if args.maintain:
        last = (store.last_maintenance.describe()
                if store.last_maintenance else "never triggered")
        print(f"[serve] maintenance: {store.maintenance_count} passes, "
              f"last: {last}")
    if health is not None:
        report = health.report()
        for line in report.render().splitlines():
            print(f"[serve] {line}")

    if args.checkpoint:
        if args.shards > 1:
            print("[serve] --checkpoint is not wired for sharded stores yet")
        else:
            path = store.save(args.checkpoint, registry=registry)
            print(f"[serve] checkpointed store+properties -> {path}")

    if args.metrics:
        print("[serve] --- metrics " + "-" * 47)
        print(obs.get_registry().render_table())
        ks = obs.kernel_summary()
        if ks:
            print("[serve] --- kernel dispatch stats " + "-" * 33)
            for key, st in sorted(ks.items()):
                steady = st["steady_s"] / max(1, st["steady_calls"])
                print(f"[serve] {key:44s} calls={st['calls']:<5d} "
                      f"compile={st['compile_s']:.3f}s "
                      f"steady={1e3 * steady:.2f}ms")
    if args.metrics_json:
        import json
        summary = obs.get_registry().summary()
        summary["kernels"] = obs.kernel_summary()
        with open(args.metrics_json, "w") as f:
            json.dump(summary, f, indent=2, default=str)
        print(f"[serve] metrics -> {args.metrics_json}")
    if args.trace:
        path = obs.export_chrome_trace(
            args.trace, counters=obs.get_registry().counters())
        print(f"[serve] chrome trace -> {path} "
              f"({len(obs.trace.events())} events)")


if __name__ == "__main__":
    main()

"""One run of one cell of ``BENCHMARK.json``: set-up, measured window, check.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name:

* ``bench/configs/<config>.json``: the deployment (graph generator and
  scale, store options, analytics' parameters, guarantees);
* ``bench/traffic/<traffic>.json``: the steps of one round of requests,
  read by ``bench.generator``;
* ``bench/metrics/<metric>.py``: a reader with ``read(run)`` that returns
  the metric's value from the run's record, or None where it finds
  nothing to read;
* ``bench/limits.json``: the limit of each number the check compares.

The served path is the program's public API, built as
``repro.launch.serve.build_service`` builds it: ``GraphStore.from_edges``
(forward and transpose views, no hashing, a ``MaintenancePolicy``) or,
where the configuration's ``store.shards`` is above 1,
``ShardedGraphStore.from_edges`` (the same views, vertex-partitioned over
a ``("shard",)`` mesh of the cell's chips, one shard each, every epoch one
``shard_map`` program) -> ``PropertyRegistry`` with the analytics the mix
reads, lazily (a single store only) -> ``RequestPipeline``.  The window is
a closed loop with one client: each request is sent when the one before it
has completed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import reference, spans, trace_reduce
from .generator import Graph, Rounds, arcs

WARMUP_ROUNDS = 2
# a sharded store sizes its routing buckets from each batch, with caps that
# only ratchet up, and specializes its epoch program to them: its warm-up
# goes on until this many rounds in a row meet no new program, or until
# SHARDED_WARMUP_MAX rounds in all
SHARDED_QUIET_ROUNDS = 20
SHARDED_WARMUP_MAX = 80
# rounds drawn ahead of the window, as a multiple of the rounds that the
# warm-up's pace says the window will need
DRAW_AHEAD = 1.5
# spans of a traced window named in the log, most self time first
SLOW_SPANS = 8
# seconds of the window that the traced run also records with the profiler
PROFILE_S = 3.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# SlabGraph lanes at or above this hold no edge: tombstone, empty, padding
_FIRST_SENTINEL = 0xFFFF_FFFD


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """The benchmark's files under one checkout root."""

    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.dir = self.root / "bench"
        self.spec = _json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _json(self.root / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _json(self.dir / "traffic" / f"{name}.json")

    def limits(self) -> dict:
        return _json(self.dir / "limits.json")

    def peaks(self, device_kind: str) -> dict:
        table = _json(self.dir / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(f"device {device_kind!r} is not in "
                           f"bench/peaks.json")
        return table["devices"][device_kind]

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The metrics the cell reports: its per-layer ones when traced,
        else its end-to-end ones."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> Callable:
        path = self.dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the record of one run's window."""
    setup_s: float
    window_s: float
    #: per window round: ``t0``/``t1`` (host clock), ``edges`` (inserted
    #: plus deleted, as the store reported them, in edges: an undirected
    #: edge's two arcs count once) and ``requests``, each a dict with
    #: ``kind``, ``name`` and blocked ``seconds``; in a traced run of a
    #: sharded store an update also has ``counters``: the program's
    #: ``obs.metrics`` counters once it completed, counted from the window's
    #: start
    rounds: List[dict]
    #: the program's ``obs`` spans over the window (traced runs)
    spans: List[spans.Span] = dataclasses.field(default_factory=list)
    #: ``trace_reduce`` of the profiled part of the window (traced runs)
    device: Optional[dict] = None

    def requests(self, kind: str, name: Optional[str] = None) -> List[dict]:
        return [q for r in self.rounds for q in r["requests"]
                if q["kind"] == kind and (name is None or q["name"] == name)]


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

def _most(traffic: dict, what: str) -> int:
    """The most edges one update step of the mix deletes or inserts."""
    return max([int(s[what]) for s in traffic["round"]
                if s["kind"] == "update"] or [0])


def shards_of(config: dict) -> int:
    """The shards the configuration's store is partitioned into (1: one
    ``GraphStore``)."""
    return int(config["store"].get("shards", 1))


def build_store(graph: Graph, config: dict, traffic: dict, src, dst,
                devices=()):
    """The store over the edges ``src, dst`` (both arcs of each where the
    graph is undirected): a ``ShardedGraphStore`` with one shard on each of
    ``devices`` where the configuration asks for shards, else a
    ``GraphStore``."""
    from repro.stream import GraphStore, MaintenancePolicy, ShardedGraphStore

    opts = config["store"]
    inserts = graph.arcs_per_edge * _most(traffic, "inserts")
    src, dst = arcs(src, dst, graph.directed)
    maintenance = MaintenancePolicy(tombstone_ratio=opts["tombstone_ratio"])
    shards = shards_of(config)
    if shards == 1:
        return GraphStore.from_edges(
            graph.n_vertices, src, dst, hashing=opts["hashing"],
            with_symmetric=opts["with_symmetric"],
            slack_slabs=inserts // 64 + 512, maintenance=maintenance)
    if shards != len(devices):
        raise ValueError(f"{shards} shards need one chip each; the cell "
                         f"has {len(devices)}")
    from repro.distributed.sharded_graph import shard_mesh
    return ShardedGraphStore.from_edges(
        graph.n_vertices, shards, src, dst, with_transpose=True,
        with_symmetric=opts["with_symmetric"],
        slack_slabs=inserts // 64 + 512, maintenance=maintenance,
        mesh=shard_mesh(shards))


def build_service(store, graph: Graph, config: dict, traffic: dict):
    """(registry, pipeline) over ``store``: the registry computes each
    analytic the mix reads once here."""
    from repro.algorithms import (pagerank_stream_property,
                                  wcc_stream_property)
    from repro.stream import PropertyRegistry, RequestPipeline

    opts = config["store"]
    reads = dict.fromkeys(s["name"] for s in traffic["round"]
                          if s["kind"] == "property")
    if reads and shards_of(config) > 1:
        # the single store's analytics would run on the stacked shards
        raise ValueError(f"the harness serves no analytic {list(reads)} "
                         f"of a sharded store yet")
    registry = PropertyRegistry(store)
    for name in reads:
        if name == "pagerank":
            spec = pagerank_stream_property(
                damping=config["analytics"]["pagerank"]["damping"])
        elif name == "wcc":
            # the union's edge list must hold every live arc; a round
            # deletes as many edges as it inserts, so the live count stays
            # under the pairs generated plus one round's inserts
            spec = wcc_stream_property(cap=graph.arcs_per_edge * (
                graph.n_generated + sum(
                    int(s["inserts"]) for s in traffic["round"]
                    if s["kind"] == "update")) + 4096)
        else:
            raise ValueError(f"no analytic {name!r}")
        registry.register(spec, policy=opts["policy"])
    return registry, RequestPipeline(store, registry)


def _request(item: dict):
    from repro.stream import MembershipQuery, PropertyRead, UpdateBatch
    if item["kind"] == "update":
        return UpdateBatch(**item["request"])
    if item["kind"] == "member":
        src, dst = item["request"]
        return MembershipQuery(src=src, dst=dst)
    return PropertyRead(item["name"])


def serve_round(pipeline, rnd: dict, annotate, arcs_per_edge: int,
                counters=None) -> dict:
    """Serve one round, one request at a time; each request is timed from
    submission to a result the host holds or the device has finished.
    ``counters``, where given, is read after each update and kept with
    it."""
    import jax
    out = []
    t0 = time.perf_counter()
    for item in rnd["requests"]:
        req = _request(item)
        name = item.get("name", item["kind"])
        with annotate(name):
            t = time.perf_counter()
            resp = pipeline.run([req])[0]
            jax.block_until_ready(resp.payload.get("value"))
            dt = time.perf_counter() - t
        out.append({"kind": item["kind"], "name": name, "seconds": dt,
                    "response": resp})
        if counters is not None and item["kind"] == "update":
            out[-1]["counters"] = counters()
    t1 = time.perf_counter()
    edges = sum(q["response"].payload.get("inserted", 0)
                + q["response"].payload.get("deleted", 0)
                for q in out if q["kind"] == "update") / arcs_per_edge
    return {"t0": t0, "t1": t1, "edges": edges, "requests": out}


def _views_ready(store) -> None:
    import jax
    jax.block_until_ready(store.views)


def _pool_rows(store) -> str:
    """Each view's pool capacity in slab rows (per shard where sharded): a
    change across the window means the pools grew and every program over
    them met new shapes."""
    return " ".join(f"{name} {getattr(g, 'graphs', g).keys.shape[-2]}"
                    for name, g in store.views.items())


def _store_keys(store, view: str, swap: bool) -> np.ndarray:
    """The view's live keys, sorted, read off its pool arrays: each lane
    holds a neighbour of its slab's owner; ``swap`` for the transpose view,
    whose owner is the edge's destination.  A sharded view is read shard by
    shard: shard ``k`` of ``S`` holds its owners as local ids, global
    ``local * S + k``, and its neighbours as global ids."""
    import jax
    g = store.views[view]
    stacked = getattr(g, "graphs", None)
    if stacked is None:
        parts = [jax.device_get((g.keys, g.slab_vertex))]
        n = 1
    else:
        keys, owner = jax.device_get((stacked.keys, stacked.slab_vertex))
        parts, n = list(zip(keys, owner)), g.n_shards
    a, b = [], []
    for k, (keys, owner) in enumerate(parts):
        rows, lanes = np.nonzero((owner >= 0)[:, None]
                                 & (keys < _FIRST_SENTINEL))
        a.append(owner[rows].astype(np.int64) * n + k)
        b.append(keys[rows, lanes])
    a, b = np.concatenate(a), np.concatenate(b)
    return np.sort(reference.keys_of(b, a) if swap
                   else reference.keys_of(a, b))


def _mismatch(got: np.ndarray, want: np.ndarray) -> int:
    """Pairs in one sorted key list and not the other, duplicates in
    ``got`` counted too; ``want`` is sorted and distinct."""
    uniq = got[np.concatenate([[True], got[1:] != got[:-1]])] \
        if len(got) else got
    dup = len(got) - len(uniq)
    if len(uniq) == len(want) and np.array_equal(uniq, want):
        return dup
    return int(dup + len(np.setxor1d(uniq, want, assume_unique=True)))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class _Compiles:
    """Counts XLA compilations.  JAX reports a backend compile also where
    the persistent cache answers it; those hits are not compilations."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        self.seconds = 0.0

    @property
    def n(self) -> int:
        return self.compiles - self.hits

    def duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def event(self, event, **_):
        self.hits += event == _CACHE_HIT


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, devices, t_start: float,
             log: Callable[[str], None], control=None) -> dict:
    """Run ``workload`` once and return its result line (a dict).

    ``t_start`` is the process start on the ``time.perf_counter`` clock;
    set-up runs from there to the start of the window.  ``control``
    (``bench.control.answers``) adds, under ``control``, what the check
    says when the control's answers take the place of the program's.
    """
    import jax
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.event)
    try:
        return _run(bench, workload, seed, seconds, trace, devices=devices,
                    t_start=t_start, log=log, compiles=compiles,
                    control=control)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles.duration)
        jax.monitoring.unregister_event_listener(compiles.event)


def _run(bench, workload, seed, seconds, trace, *, devices, t_start, log,
         compiles, control) -> dict:
    import jax
    from repro import obs

    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    phase = [time.perf_counter(), compiles.seconds]
    log(f"set-up start {phase[0] - t_start:.3f} s after the process")

    def lap(what: str) -> None:
        now = time.perf_counter()
        log(f"set-up {what} {now - phase[0]:.3f} s (XLA compile "
            f"{compiles.seconds - phase[1]:.3f} s)")
        phase[:] = now, compiles.seconds

    # -- set-up ---------------------------------------------------------------
    graph = Graph(config, seed)
    lap("label permutation")
    src, dst = graph.edges()
    lap(f"generate V={graph.n_vertices} E={len(src)}")
    rounds = Rounds(graph, traffic, src, dst)
    lap("ledger")
    store = build_store(graph, config, traffic, src, dst, devices)
    del src, dst
    _views_ready(store)
    lap("store build")
    registry, pipeline = build_service(store, graph, config, traffic)
    jax.block_until_ready(registry.states())
    lap(f"analytics init {registry.names()}")
    def untraced(_):
        return contextlib.nullcontext()

    per_edge = graph.arcs_per_edge
    served = []
    rounds.draw(WARMUP_ROUNDS)
    for rnd in rounds.rounds:
        served.append(serve_round(pipeline, rnd, untraced, per_edge))
    quiet = 0
    while shards_of(config) > 1 and quiet < SHARDED_QUIET_ROUNDS \
            and len(served) < SHARDED_WARMUP_MAX:
        met = compiles.compiles
        rounds.draw(1)
        served.append(serve_round(pipeline, rounds.rounds[-1], untraced,
                                  per_edge))
        quiet = quiet + 1 if compiles.compiles == met else 0
    # the pace of a round once its programs are compiled
    pace = min(r["t1"] - r["t0"] for r in served[1:])
    deletes = sum(int(s["deletes"]) for s in traffic["round"]
                  if s["kind"] == "update")
    ratio = config["store"]["tombstone_ratio"]
    # rounds between compactions: the policy compacts once tombstones are
    # ``ratio`` of the occupied lanes
    cycle = ratio / (1.0 - ratio) * len(rounds.ledger) / max(deletes, 1)
    if pace * cycle < DRAW_AHEAD * seconds:
        # a maintenance pass falls inside the window: compile it here, then
        # time a second one and add its share to the pace
        store.maintain("compact")
        _views_ready(store)
        t = time.perf_counter()
        store.maintain("compact")
        _views_ready(store)
        pace += (time.perf_counter() - t) / cycle
        rounds.draw(1)
        served.append(serve_round(pipeline, rounds.rounds[-1], untraced,
                                  per_edge))
    ahead = math.ceil(DRAW_AHEAD * seconds / max(pace, 1e-3)) + 2
    _views_ready(store)
    lap(f"warm-up {len(served)} rounds, {pace:.3f} s a round")
    rounds.draw(ahead)
    lap(f"draw {ahead} rounds ahead")

    # -- the window -----------------------------------------------------------
    profile_dir = tempfile.mkdtemp(prefix="bench_profile_") if trace else None
    annotate = jax.profiler.TraceAnnotation if trace else untraced
    # a sharded store counts each epoch's routing per shard in the
    # program's metrics registry, which is kept only while it is on
    counted = trace and shards_of(config) > 1
    counters = obs.get_registry().counters if counted else None
    if counted:
        obs.get_registry().reset()
        obs.metrics.enable()
    if trace:
        obs.trace.reset()
        obs.trace.enable()
        jax.profiler.start_trace(profile_dir)
        traced = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        traced.__enter__()
    n_compiles = compiles.n
    rows_before = _pool_rows(store)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    window, drawn_in_window, met = [], 0.0, []
    while True:
        if len(served) == len(rounds.rounds):
            t = time.perf_counter()
            rounds.draw(1)
            drawn_in_window += time.perf_counter() - t
        before = compiles.compiles
        rec = serve_round(pipeline, rounds.rounds[len(served)],
                          lambda kind: annotate(f"bench.{kind}"), per_edge,
                          counters)
        if compiles.compiles > before:
            met.append(f"{len(window)}:{compiles.compiles - before}")
        served.append(rec)
        window.append(rec)
        if trace and traced is not None and \
                time.perf_counter() - t0 >= PROFILE_S:
            _views_ready(store)
            traced.__exit__(None, None, None)
            traced = None
            jax.profiler.stop_trace()
        if time.perf_counter() >= deadline:
            break
    _views_ready(store)
    t1 = time.perf_counter()
    in_window = compiles.n - n_compiles
    if counted:
        obs.metrics.disable()
    if trace:
        obs.trace.disable()
        if traced is not None:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
    paces = sorted(r["t1"] - r["t0"] for r in window)
    log(f"window {t1 - t0:.3f} s, {len(window)} rounds (median "
        f"{paces[len(paces) // 2]:.3f} s, slowest {paces[-1]:.3f} s), "
        f"{in_window} compilations, {drawn_in_window:.3f} s drawing rounds")
    log(f"window programs met anew (compiled or loaded from the cache), "
        f"round:count: {' '.join(met) or 'none'}")
    log(f"window pool rows {rows_before}; after {_pool_rows(store)}")
    for name in dict.fromkeys(q["name"] for q in window[0]["requests"]):
        times = sorted(q["seconds"] for r in window for q in r["requests"]
                       if q["name"] == name)
        log(f"window {name}: {len(times)} requests, median "
            f"{times[len(times) // 2]:.4f} s, min {times[0]:.4f} s, max "
            f"{times[-1]:.4f} s")
    run = Run(setup_s=setup_s, window_s=t1 - t0, rounds=window)
    if trace:
        run.spans = spans.from_events(obs.trace.events())
        obs.trace.reset()
        slow = sorted(run.spans, key=lambda x: -x.self_ns)[:SLOW_SPANS]
        log("window spans of most self time, self/whole ms: " + ", ".join(
            f"{x.name} {x.self_ns / 1e6:.1f}/{x.ns / 1e6:.1f}"
            for x in slow))
        pb = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"))
        run.device = trace_reduce.reduce_file(pb[-1])
        shutil.rmtree(profile_dir, ignore_errors=True)

    # -- device record, then the check ----------------------------------------
    dev = devices[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    t = time.perf_counter()
    views = {name: _store_keys(store, name, swap=name == "transpose")
             for name in store.views}
    del store, registry, pipeline
    live = reference.replay_live(
        rounds.initial_keys,
        [e for rnd in rounds.rounds[:len(served)] for e in rnd["epochs"]])
    live = reference.arc_keys(live, graph.directed)
    checks, failed = check(bench, config, rounds, served, window, views,
                           graph.n_vertices, live)
    log(f"check {time.perf_counter() - t:.3f} s")
    if control is not None:
        ctl = check(bench, config, rounds, control(
            config=config, rounds=rounds, served=served,
            n_vertices=graph.n_vertices, live=live), window, views,
            graph.n_vertices, live)[0]
        controls = {"correct": _correct(ctl), "checks": ctl}

    metrics = {}
    for m in bench.metrics(workload, trace):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": _correct(checks),
              "attempted": sum(len(r["requests"]) for r in window),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.device["busy_s"]
        device["window_s"] = run.device["window_s"]
        result["breakdown"] = {k: run.device[k]
                               for k in ("device_ops", "idle_gaps")}
    if control is not None:
        result["control"] = controls
    result["checks"] = checks
    return result


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def _correct(checks: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in checks.values())


def check(bench: Bench, config: dict, rounds: Rounds, served: List[dict],
          window: List[dict], views: Dict[str, np.ndarray], n_vertices: int,
          live: np.ndarray):
    """Compare every served answer, and the sorted (src, dst) keys of each
    of the store's views, with the reference; ``live`` holds the sorted
    keys of the live arcs that the reference replays.  Returns the compared
    numbers, each with its limit, and the number of window requests whose
    answer was wrong or an error."""
    limits = bench.limits()
    wrong_updates = wrong_reads = errors = 0
    failed = 0
    last_values = {}
    for rnd, rec in zip(rounds.rounds, served):
        in_window = any(rec is w for w in window)
        for item, q in zip(rnd["requests"], rec["requests"]):
            resp = q["response"]
            bad = resp.kind == "error" or resp.payload.get("stale", False)
            errors += bad
            if item["kind"] == "update" and not bad:
                got = {k: resp.payload[k] for k in item["expect"]}
                bad = got != item["expect"]
                wrong_updates += bad
            elif item["kind"] == "member" and not bad:
                n = int((np.asarray(resp.payload["found"])
                         != item["expect"]).sum())
                wrong_reads += n
                bad = n > 0
            elif item["kind"] == "property" and not bad:
                last_values[item["name"]] = resp.payload["value"]
            failed += bool(bad) and in_window
    import jax
    last_values = jax.device_get(last_values)
    checks = {"error_responses": errors, "update_count_mismatch":
              wrong_updates}
    if any(i["kind"] == "member" for r in rounds.rounds for i in
           r["requests"]):
        checks["member_mismatch"] = wrong_reads
    checks["edge_set_mismatch"] = sum(_mismatch(k, live)
                                      for k in views.values())
    src, dst = reference.split_keys(live)
    if "pagerank" in last_values:
        pr_ref = reference.ref_pagerank(
            n_vertices, src, dst,
            damping=config["analytics"]["pagerank"]["damping"])
        pr = np.asarray(last_values["pagerank"], np.float64)
        checks["pagerank_l1"] = float(np.abs(pr - pr_ref).sum()) \
            if np.isfinite(pr).all() else float("inf")
    if "wcc" in last_values:
        comp = reference.partition_of(last_values["wcc"])
        checks["wcc_mismatch"] = int(
            (comp != reference.ref_components(n_vertices, src, dst)).sum())
    return ({k: {"value": v, "limit": limits[k]} for k, v in checks.items()},
            failed)

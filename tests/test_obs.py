"""Telemetry-plane tests (repro.obs, DESIGN.md §10).

Coverage planes:

* units — histogram exact percentiles + bucket ladder, counter/gauge/
  event registry, span nesting and Chrome trace-event export schema,
  ``@timed_dispatch`` compile-vs-steady accounting and its trace /
  reentrancy guards;
* NEUTRALITY (the acceptance contract) — pools are leaf-for-leaf
  bit-identical with telemetry on vs off, for both ``GraphStore`` and
  ``ShardedGraphStore``, across a mixed churn epoch sequence including a
  maintenance pass: instrumentation only reads clocks and blocks on
  already-computed results, never changes a value;
* zero-overhead-when-off — the disabled fast path stays within a
  generous constant factor of un-instrumented dispatch;
* counters that ride spans (``obs.count``) and the device-side counts
  behind them — probe hops/visits against a host walk of the chains,
  WCC hooking rounds against a Python replay of the union, PageRank
  iterations — read in one transfer per span, and toggling tracing
  recompiles nothing.
"""
import gc
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with the plane disarmed and empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ============================================================================
# metrics units
# ============================================================================

class TestMetrics:
    def test_histogram_exact_percentiles(self):
        h = obs.Histogram()
        for v in range(1, 101):                  # 1..100 ms
            h.record(v / 1000.0)
        assert h.count == 100
        assert h.percentile(50) == pytest.approx(0.050, rel=0.03)
        assert h.percentile(95) == pytest.approx(0.095, rel=0.03)
        assert h.percentile(99) == pytest.approx(0.099, rel=0.03)
        assert h.min == pytest.approx(0.001)
        assert h.max == pytest.approx(0.100)
        assert h.mean == pytest.approx(0.0505)
        s = h.summary()
        assert s["count"] == 100 and s["p99_s"] >= s["p50_s"]

    def test_histogram_saturation_falls_back_to_buckets(self):
        h = obs.Histogram(sample_cap=8)
        for v in [0.001] * 50 + [0.016] * 50:
            h.record(v)
        assert h.saturated
        # bucket-midpoint estimate: right order of magnitude, not exact
        assert 0.0002 < h.percentile(50) < 0.05
        assert h.count == 100

    def test_registry_counters_gauges_events(self):
        reg = obs.MetricsRegistry()
        reg.counter("a").inc()
        reg.counter("a").inc(4)
        reg.gauge("g").set(2.5)
        reg.event("ping", shard=3)
        assert reg.counters()["a"] == 5
        assert reg.summary()["gauges"]["g"] == 2.5
        evs = reg.events("ping")
        assert len(evs) == 1 and evs[0]["shard"] == 3
        assert evs[0]["seq"] == 1

    def test_module_helpers_are_noops_when_off(self):
        obs.inc("never")
        obs.observe("never", 1.0)
        obs.set_gauge("never", 1.0)
        obs.emit_event("never")
        assert obs.get_registry().counters() == {}
        obs.metrics.enable()
        obs.inc("now")
        assert obs.get_registry().counters()["now"] == 1

    def test_render_table_smoke(self):
        obs.metrics.enable()
        obs.observe("lat", 0.002)
        obs.inc("n")
        table = obs.get_registry().render_table()
        assert "lat" in table and "p99" in table and "n" in table


# ============================================================================
# trace units + Chrome export schema
# ============================================================================

class TestTrace:
    def test_spans_emit_matched_b_e_pairs(self):
        obs.trace.enable()
        with obs.span("outer", version=3):
            with obs.span("inner"):
                pass
        evs = obs.trace.events()
        assert [e["ph"] for e in evs] == ["B", "B", "E", "E"]
        assert [e["name"] for e in evs] == ["outer", "inner",
                                            "inner", "outer"]
        assert evs[0]["args"]["version"] == 3
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)                  # monotonic within thread

    def test_span_annotate_rides_the_close_event(self):
        obs.trace.enable()
        with obs.span("s") as sp:
            sp.annotate(inserted=7)
        evs = obs.trace.events()
        assert evs[-1]["args"]["inserted"] == 7

    def test_disabled_span_is_the_shared_noop(self):
        s1 = obs.span("a", version=1)
        s2 = obs.span("b")
        assert s1 is s2                          # no allocation when off
        with s1:
            pass
        assert obs.trace.events() == []

    def test_chrome_export_schema(self, tmp_path):
        obs.trace.enable()
        with obs.span("epoch", version=1):
            obs.instant("witness", over=2)
        path = tmp_path / "trace.json"
        obs.export_chrome_trace(path, counters={"kernel.calls": 5})
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        for e in evs:
            assert {"ph", "name", "ts", "pid"} <= set(e)
        phs = [e["ph"] for e in evs]
        assert phs.count("B") == phs.count("E") == 1
        assert "i" in phs and "C" in phs
        c = next(e for e in evs if e["ph"] == "C")
        assert c["args"]["value"] == 5.0


# ============================================================================
# @timed_dispatch units
# ============================================================================

class TestTimedDispatch:
    def test_compile_vs_steady_accounting(self):
        calls = []

        @obs.timed_dispatch("fam")
        def op(x):
            calls.append(1)
            return x + 1

        obs.metrics.enable()
        for i in range(4):
            assert op(jnp.float32(i)) == i + 1
        stats = obs.kernel_stats()[("fam", "op", "scalar")]
        assert stats["calls"] == 4
        assert stats["steady_calls"] == 3        # first call = compile slot
        assert stats["compile_s"] >= 0.0
        summary = obs.kernel_summary()
        assert "fam.op[scalar]" in summary
        counters = obs.get_registry().counters()
        assert counters["kernel.fam.op.calls"] == 4

    def test_disabled_is_pass_through(self):
        @obs.timed_dispatch("fam")
        def op(x):
            return x * 2

        assert op(3) == 6
        assert obs.kernel_stats() == {}

    def test_reentrancy_guard_records_only_outermost(self):
        @obs.timed_dispatch("fam")
        def inner(x):
            return x + 1

        @obs.timed_dispatch("fam")
        def outer(x):
            return inner(x) + 1

        obs.metrics.enable()
        assert outer(jnp.float32(0)) == 2
        stats = obs.kernel_stats()
        assert ("fam", "outer", "scalar") in stats
        assert ("fam", "inner", "scalar") not in stats

    def test_trace_guard_steps_aside_under_jit(self):
        @obs.timed_dispatch("fam")
        def op(x):
            return x + 1

        obs.metrics.enable()
        out = jax.jit(lambda x: op(x))(jnp.float32(1))
        assert out == 2                          # no block on tracers
        assert obs.kernel_stats() == {}          # and no bogus timing

    def test_kernel_entry_points_record(self):
        from repro.stream import GraphStore
        rng = np.random.default_rng(0)
        src = rng.integers(0, 50, 200).astype(np.uint32)
        dst = rng.integers(0, 50, 200).astype(np.uint32)
        obs.enable()
        store = GraphStore.from_edges(50, src, dst)
        store.apply(ins_src=[1, 2], ins_dst=[3, 4])
        keys = list(obs.kernel_summary())
        assert any(k.startswith("slab_update.update_views") for k in keys)


# ============================================================================
# NEUTRALITY — pools bit-identical with telemetry on vs off
# ============================================================================

def _churn_epochs(store, rng, V, ledger, *, epochs):
    for _ in range(epochs):
        pool = np.array(sorted(ledger), np.uint32)
        di = rng.choice(len(pool), min(250, len(pool)), replace=False)
        dels = pool[di]
        ins = rng.integers(0, V, (350, 2)).astype(np.uint32)
        ledger -= {(int(a), int(b)) for a, b in dels}
        ledger |= {(int(a), int(b)) for a, b in ins}
        store.apply(ins_src=ins[:, 0], ins_dst=ins[:, 1],
                    del_src=dels[:, 0], del_dst=dels[:, 1])


def _pool_leaves(store):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(store.views)]


class TestNeutrality:
    V = 300

    def _drive_graph_store(self, enabled):
        from repro.stream import (GraphStore, MaintenancePolicy,
                                  PropertyRegistry, RequestPipeline)
        from repro.stream.requests import (MembershipQuery, PropertyRead,
                                           UpdateBatch)
        from repro.algorithms import pagerank_stream_property
        obs.reset()
        obs.disable()
        if enabled:
            obs.enable()
        rng = np.random.default_rng(7)
        V = self.V
        src = rng.integers(0, V, 2500).astype(np.uint32)
        dst = rng.integers(0, V, 2500).astype(np.uint32)
        store = GraphStore.from_edges(
            V, src, dst, hashing=False,
            maintenance=MaintenancePolicy(tombstone_ratio=0.1))
        _churn_epochs(store, rng, V,
                      set(zip(src.tolist(), dst.tolist())), epochs=6)
        assert store.maintenance_count > 0       # maintenance exercised
        registry = PropertyRegistry(store)
        registry.register(pagerank_stream_property())
        pipe = RequestPipeline(store, registry)
        pipe.run([UpdateBatch(ins_src=[1, 2], ins_dst=[3, 4]),
                  MembershipQuery([1, 2], [3, 4]),
                  PropertyRead("pagerank")])
        return _pool_leaves(store)

    def _drive_sharded_store(self, enabled):
        from repro.stream import MaintenancePolicy, ShardedGraphStore
        obs.reset()
        obs.disable()
        if enabled:
            obs.enable()
        rng = np.random.default_rng(8)
        V = self.V
        src = rng.integers(0, V, 2500).astype(np.uint32)
        dst = rng.integers(0, V, 2500).astype(np.uint32)
        store = ShardedGraphStore.from_edges(
            V, 4, src, dst,
            maintenance=MaintenancePolicy(tombstone_ratio=0.1))
        _churn_epochs(store, rng, V,
                      set(zip(src.tolist(), dst.tolist())), epochs=6)
        assert store.maintenance_count > 0
        return _pool_leaves(store)

    def test_graph_store_pools_identical_on_vs_off(self):
        off = self._drive_graph_store(False)
        on = self._drive_graph_store(True)
        # the traced run read the device counters back, the other did not
        counted = obs.trace.span_counters()
        assert counted["store.apply.dispatch"] and counted["store.query"]
        assert counted["pagerank.solve"]
        assert len(off) == len(on)
        for a, b in zip(off, on):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b)

    def test_sharded_store_pools_identical_on_vs_off(self):
        off = self._drive_sharded_store(False)
        on = self._drive_sharded_store(True)
        assert len(off) == len(on)
        for a, b in zip(off, on):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b)

    def test_enabled_run_actually_collected_telemetry(self):
        self._drive_graph_store(True)
        counters = obs.get_registry().counters()
        assert counters.get("store.apply.epochs", 0) > 0
        assert any(k.startswith("kernel.") for k in counters)
        assert len(obs.trace.events()) > 0
        obs.reset()


# ============================================================================
# zero-overhead-when-off guard
# ============================================================================

class TestNoopOverhead:
    def test_disabled_dispatch_overhead_bounded(self):
        import time

        def bare(x):
            return x

        @obs.timed_dispatch("fam")
        def wrapped(x):
            return x

        # warm both paths, then compare medians over many trials; the
        # bound is deliberately generous (scheduler noise on shared CI)
        def med(fn):
            ts = []
            for _ in range(7):
                t0 = time.perf_counter()
                for _ in range(20000):
                    fn(1)
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return ts[len(ts) // 2]

        med(bare), med(wrapped)                  # warmup
        assert med(wrapped) < 30 * med(bare) + 0.05

    def test_disabled_span_and_helpers_cost_nothing_observable(self):
        with obs.span("x", a=1) as sp:
            obs.count("x", 1)
            obs.count("x", jnp.int32(2))
            assert sp.sync_on(jnp.zeros(2)) is sp
        obs.instant("x")
        obs.observe("x", 1.0)
        assert obs.trace.events() == []
        assert obs.trace.span_counters() == {}
        assert obs.get_registry().counters() == {}

    def test_disabled_count_overhead_bounded(self):
        import time

        def bare(name, value):
            return None

        def med(fn):
            ts = []
            for _ in range(7):
                t0 = time.perf_counter()
                for _ in range(20000):
                    fn("x", 1)
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return ts[len(ts) // 2]

        med(bare), med(obs.count)                # warmup
        assert med(obs.count) < 30 * med(bare) + 0.05


# ============================================================================
# counters that ride spans
# ============================================================================

def _closed(name):
    return [e for e in obs.trace.events()
            if e["ph"] == "E" and e["name"] == name]


class TestCounters:
    def test_count_rides_the_innermost_span(self):
        obs.trace.enable()
        with obs.span("outer"):
            obs.count("a", 1)
            with obs.span("inner", tag=3):
                obs.count("a", 2)
                obs.count("a", jnp.int32(5))
                obs.count("b", 0.5)
            obs.count("a", 10)
        obs.count("orphan", 1)                   # no open span: dropped
        assert _closed("inner")[0]["args"] == {"tag": 3, "a": 7, "b": 0.5}
        assert _closed("outer")[0]["args"] == {"a": 11}
        assert obs.trace.span_counters() == {
            "inner": [{"a": 7, "b": 0.5}], "outer": [{"a": 11}]}

    def test_counters_outlive_reset_until_the_next_session(self):
        obs.trace.enable()
        with obs.span("s"):
            obs.count("a", 1)
        obs.trace.disable()
        obs.trace.reset()
        assert obs.trace.events() == []
        assert obs.trace.span_counters() == {"s": [{"a": 1}]}
        obs.trace.enable()
        assert obs.trace.span_counters() == {}

    def test_device_counters_read_once_per_span(self, monkeypatch):
        calls = []
        real = jax.device_get

        def counting(tree):
            calls.append(1)
            return real(tree)

        monkeypatch.setattr(jax, "device_get", counting)
        obs.trace.enable()
        with obs.span("s"):
            obs.count("a", jnp.int32(1))
            obs.count("b", jnp.int32(2))
        assert len(calls) == 1
        calls.clear()
        x, four = jnp.arange(3), jnp.int32(4)
        with obs.span("t") as sp:                # the span's own read
            obs.count("a", four)
            got = sp.fetch(x)
        assert len(calls) == 1 and list(got) == [0, 1, 2]
        assert _closed("t")[0]["args"] == {"a": 4}

    def test_store_reads_answers_and_counters_in_one_transfer(
            self, monkeypatch):
        """Tracing adds no device-to-host transfer to a store call: the
        counters ride the read the call makes anyway."""
        from repro.stream import GraphStore
        rng = np.random.default_rng(2)
        src = rng.integers(0, 60, 300).astype(np.uint32)
        dst = rng.integers(0, 60, 300).astype(np.uint32)
        store = GraphStore.from_edges(60, src, dst)
        calls = []
        real = jax.device_get

        def counting(tree):
            calls.append(1)
            return real(tree)

        def transfers(traced):
            (obs.trace.enable if traced else obs.trace.disable)()
            calls.clear()
            store.apply(ins_src=rng.integers(0, 60, 5),
                        ins_dst=rng.integers(0, 60, 5),
                        del_src=src[:3], del_dst=dst[:3])
            store.query(src[:9], dst[:9])
            return len(calls)

        transfers(False)                         # compile every shape
        monkeypatch.setattr(jax, "device_get", counting)
        assert transfers(False) == transfers(True) == 2
        counted = obs.trace.span_counters()
        assert counted["store.apply.dispatch"] and counted["store.query"]

    def test_sync_on_ends_the_span_after_the_work(self):
        obs.trace.enable()
        x = jnp.arange(1 << 12, dtype=jnp.float32)
        with obs.span("s") as sp:
            y = jnp.sin(x) * 2
            sp.sync_on(y)
        assert sp.sync is y
        assert y.is_ready()

    def test_hooks_live_only_while_tracing_is_on(self):
        from repro.obs import trace
        assert trace._on_gc not in gc.callbacks
        obs.trace.enable()
        obs.trace.enable()                       # idempotent
        assert gc.callbacks.count(trace._on_gc) == 1
        with obs.span("work"):
            jax.jit(lambda v: v * 3 + 1)(jnp.arange(7.0)).block_until_ready()
            gc.collect()
        obs.trace.disable()
        assert trace._on_gc not in gc.callbacks
        work = _closed("work")[0]["args"]
        assert work["jit.jaxpr_trace_s"] > 0
        assert work["jit.backend_compile_s"] > 0
        gcs = _closed("host.gc")
        assert gcs and gcs[-1]["args"]["generation"] == 2
        n = len(obs.trace.events())
        gc.collect()                             # off: nothing recorded
        assert len(obs.trace.events()) == n


# ============================================================================
# device-side counts against host references
# ============================================================================

def _hub_edges():
    """Vertex 0 owns one bucket with a 6-slab chain; vertices 1..40 one
    short slab each (no hashing: one bucket per vertex)."""
    hub = np.arange(1, 1 + 6 * 128 - 5)
    src = np.concatenate([np.zeros(len(hub)), np.arange(1, 41)])
    dst = np.concatenate([hub, np.arange(2, 42)])
    return src.astype(np.uint32), dst.astype(np.uint32)


def _hub_graph():
    from repro.core import from_edges_host
    return from_edges_host(64, *_hub_edges(), hashing=False)


def _host_walk(g, src, dst):
    """Per query lane, the slabs its chain walk visits (to its hit or the
    chain's end; 0 for a rejected lane)."""
    from repro.kernels.slab_update.ref import batch_valid, edge_buckets
    sj, dj = jnp.asarray(src, jnp.uint32), jnp.asarray(dst, jnp.uint32)
    valid = np.asarray(batch_valid(g, sj, dj))
    head = np.asarray(edge_buckets(g, sj, dj, jnp.asarray(valid)))
    keys, nxt = np.asarray(g.keys), np.asarray(g.next_slab)
    out = []
    for v, cur, d in zip(valid, head, np.asarray(dst, np.uint32)):
        n = 0
        while v and cur != -1:
            n += 1
            if (keys[cur] == d).any():
                break
            cur = nxt[cur]
        out.append(n)
    return np.array(out)


class TestProbeCounts:
    # lane 0 finds the hub's last edge, lane 1 misses on the hub's whole
    # chain, the rest hit or miss one-slab vertices, the pad is rejected
    SRC = [0, 0] + list(range(1, 29)) + [2, 0xFFFFFFFF]
    DST = [6 * 128 - 5, 9999] + list(range(2, 30)) + [7, 1]

    @pytest.mark.parametrize("loop", ["oracle", "packed"])
    def test_probe_matches_a_host_walk(self, loop):
        g = _hub_graph()                 # repro.core before the kernels
        from repro.kernels.slab_update import probe_counted
        from repro.kernels.slab_update.ops import probe_packed
        from repro.kernels.slab_update.ref import batch_valid, edge_buckets
        probe = {"oracle": probe_counted, "packed": probe_packed}[loop]
        sj = jnp.asarray(self.SRC, jnp.uint32)
        dj = jnp.asarray(self.DST, jnp.uint32)
        valid = batch_valid(g, sj, dj)
        found, _, _, pc = probe(g, edge_buckets(g, sj, dj, valid), dj, valid)
        walk = _host_walk(g, self.SRC, self.DST)
        assert walk[0] == walk[1] == 6 and walk.max() == 6
        assert int(pc.hops) == walk.max()
        assert int(pc.visits) == walk.sum()
        assert pc.width == len(self.SRC)
        # a batch under the packing floor walks as one whole-batch loop
        assert int(pc.rows) == walk.max() * len(self.SRC)
        assert int(pc.packs) == 0
        assert bool(found[0]) and not bool(found[1])

    def test_store_query_span_carries_the_walk(self):
        from repro.core import next_pow2
        from repro.stream import GraphStore
        store = GraphStore.from_edges(64, *_hub_edges(),
                                      with_transpose=False,
                                      with_symmetric=False)
        store.query(self.SRC[:-1], self.DST[:-1])      # compile first
        obs.trace.enable()
        got = store.query(self.SRC[:-1], self.DST[:-1])
        walk = _host_walk(store.forward, self.SRC[:-1], self.DST[:-1])
        (c,) = obs.trace.span_counters()["store.query"]
        assert c["probe.query.hops"] == walk.max()
        assert c["probe.query.visits"] == walk.sum()
        # the batch is padded up the store's power-of-two ladder
        assert c["probe.query.width"] == next_pow2(len(self.SRC) - 1)
        assert c["probe.query.rows"] == walk.max() * c["probe.query.width"]
        assert c["probe.query.packs"] == 0
        assert got[0] and not got[1]

    def test_dispatch_span_counts_every_probe_loop(self):
        from repro.stream import GraphStore
        rng = np.random.default_rng(3)
        src = rng.integers(0, 80, 600).astype(np.uint32)
        dst = rng.integers(0, 80, 600).astype(np.uint32)
        store = GraphStore.from_edges(80, src, dst)
        obs.trace.enable()
        store.apply(ins_src=[1, 2, 3], ins_dst=[4, 5, 6],
                    del_src=src[:5], del_dst=dst[:5])
        (c,) = obs.trace.span_counters()["store.apply.dispatch"]
        loops = {k.rsplit(".", 1)[0] for k in c if k.startswith("probe.")}
        assert loops == {f"probe.{r}.{op}" for r in
                         ("forward", "transpose", "symmetric")
                         for op in ("delete", "insert")} | {
            "probe.symmetric.reverse"}
        for loop in loops:
            hops, visits, width, rows, packs = (
                c[f"{loop}.{k}"]
                for k in ("hops", "visits", "width", "rows", "packs"))
            assert 1 <= hops and 0 < visits <= rows <= hops * width
            assert packs >= 0
        assert c["probe.symmetric.insert.width"] == \
            2 * c["probe.forward.insert.width"]

    def test_sharded_dispatch_counts_over_its_shards(self):
        from repro.stream import ShardedGraphStore
        rng = np.random.default_rng(4)
        src = rng.integers(0, 90, 700).astype(np.uint32)
        dst = rng.integers(0, 90, 700).astype(np.uint32)
        store = ShardedGraphStore.from_edges(90, 4, src, dst,
                                             dispatch="vmap")
        obs.trace.enable()
        store.apply(ins_src=[1, 2, 3], ins_dst=[4, 5, 6],
                    del_src=src[:6], del_dst=dst[:6])
        (c,) = obs.trace.span_counters()["store.apply.dispatch"]
        loops = {k.rsplit(".", 1)[0] for k in c if k.startswith("probe.")}
        assert "probe.forward.delete" in loops
        assert "probe.forward.insert" in loops
        for loop in loops:
            hops, visits, width, rows, packs = (
                c[f"{loop}.{k}"]
                for k in ("hops", "visits", "width", "rows", "packs"))
            assert width % 4 == 0                # S shards x their batch
            assert 1 <= hops and 0 < visits <= rows <= hops * width
            assert packs >= 0


def _py_union_rounds(parent, u, v, mask):
    """A Python replay of ``union_batch``: hooking rounds until no edge
    joins two roots."""
    def compress(p):
        while (p != p[p]).any():
            p = p[p]
        return p

    parent = compress(parent.copy())
    active = mask & (parent[u] != parent[v])
    rounds = 0
    while active.any():
        ru, rv = parent[u], parent[v]
        diff = mask & (ru != rv) & active
        np.minimum.at(parent, np.maximum(ru, rv)[diff],
                      np.minimum(ru, rv)[diff])
        parent = compress(parent)
        active = mask & (parent[u] != parent[v])
        rounds += 1
    return parent, rounds


class TestAnalyticCounts:
    def test_hook_rounds_match_a_python_union(self):
        from repro.core.union_find import init_parents, union_batch_counted
        rng = np.random.default_rng(5)
        n = 200
        # a shuffled path plus random chords: several rounds of hooking
        u = np.concatenate([np.arange(n - 1), rng.integers(0, n, 40)])
        v = np.concatenate([np.arange(1, n), rng.integers(0, n, 40)])
        order = rng.permutation(len(u))
        u, v = u[order].astype(np.int32), v[order].astype(np.int32)
        mask = np.ones(len(u), bool)
        mask[::7] = False
        want_parent, want = _py_union_rounds(np.arange(n, dtype=np.int32),
                                             u, v, mask)
        parent, rounds = union_batch_counted(init_parents(n), u, v, mask)
        assert want >= 2 and int(rounds) == want
        assert np.array_equal(np.asarray(parent), want_parent)

    def test_wcc_refresh_span_carries_the_hook_rounds(self):
        from repro.algorithms import wcc_stream_property
        from repro.algorithms.wcc import wcc_static_counted
        from repro.stream import GraphStore, PropertyRegistry
        rng = np.random.default_rng(6)
        src = rng.integers(0, 150, 400).astype(np.uint32)
        dst = rng.integers(0, 150, 400).astype(np.uint32)
        store = GraphStore.from_edges(150, src, dst)
        reg = PropertyRegistry(store)
        reg.register(wcc_stream_property())
        store.apply(del_src=src[:4], del_dst=dst[:4])
        obs.trace.enable()
        labels = reg.read("wcc")                 # a deleting epoch: refresh
        store.apply(ins_src=[1], ins_dst=[2])
        reg.read("wcc")                          # insert only: incremental
        obs.trace.disable()
        want_labels, want = wcc_static_counted(store.forward)
        (c,) = obs.trace.span_counters()["wcc.refresh"]
        assert c["wcc.hook_rounds"] == int(want)
        names = [e["name"] for e in obs.trace.events() if e["ph"] == "E"]
        assert names.count("wcc.incremental") == 1
        assert labels.shape == want_labels.shape

    def test_pagerank_span_carries_the_solver_iterations(self):
        from repro.algorithms import pagerank, pagerank_stream_property
        from repro.stream import GraphStore, PropertyRegistry
        rng = np.random.default_rng(7)
        src = rng.integers(0, 120, 500).astype(np.uint32)
        dst = rng.integers(0, 120, 500).astype(np.uint32)
        store = GraphStore.from_edges(120, src, dst)
        reg = PropertyRegistry(store)
        reg.register(pagerank_stream_property())
        prev = reg.read("pagerank")
        store.apply(ins_src=[3, 4], ins_dst=[5, 6])
        obs.trace.enable()
        pr = reg.read("pagerank")
        obs.trace.disable()
        want_pr, want = pagerank(store.transpose, store.out_degree,
                                 init_pr=prev, rows=store.sweep_rows())
        (c,) = obs.trace.span_counters()["pagerank.solve"]
        assert c["pagerank.iters"] == int(want) > 1
        assert np.array_equal(np.asarray(pr), np.asarray(want_pr))


class TestTracingCompilesNothing:
    def test_toggling_tracing_between_calls_compiles_nothing(self):
        from repro.algorithms import (pagerank_stream_property,
                                      wcc_stream_property)
        from repro.stream import GraphStore, PropertyRegistry
        compiles = []

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        rng = np.random.default_rng(9)
        V = 160
        src = rng.integers(0, V, 700).astype(np.uint32)
        dst = rng.integers(0, V, 700).astype(np.uint32)
        store = GraphStore.from_edges(V, src, dst)
        reg = PropertyRegistry(store)
        reg.register(pagerank_stream_property())
        reg.register(wcc_stream_property())

        def step(k):
            # same shapes every call: 4 inserts, one query of 8 pairs
            store.apply(ins_src=rng.integers(0, V, 4),
                        ins_dst=rng.integers(0, V, 4))
            store.query(rng.integers(0, V, 8), rng.integers(0, V, 8))
            jax.block_until_ready((reg.read("pagerank"), reg.read("wcc")))

        for k in range(3):                       # warm every shape
            step(k)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            for k in range(4):
                if k % 2:
                    obs.trace.enable()
                else:
                    obs.trace.disable()
                step(k)
        finally:
            obs.trace.disable()
            jax.monitoring.unregister_event_duration_listener(listen)
        assert compiles == []
        assert obs.trace.span_counters()["store.apply.dispatch"]

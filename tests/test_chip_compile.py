"""Compile the served path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
``v5e:2x2`` topology that is described, not attached.  Every program is
compiled at Graph500 scale-21 widths (V = 2^21 vertices, a pool of 2^22
slabs per view, 65,536-edge update batches) and must

* fit a v5e's 16 GB of HBM per device (``memory_analysis``), and
* contain no ``tpu_custom_call``: ``impl="auto"`` took the XLA engine,
  since the v5e compiler refuses the Pallas kernels.

The sharded epoch is compiled over a described four-device mesh and must
route through an all-to-all.  The topology is described in a module
fixture, never at import: one process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import slab_graph as SG
from repro.distributed.sharded_graph import (graph_pspecs, max_owner_count,
                                             routing_cap_blocks, shard_empty)
from repro.kernels.slab_compact import ops as compact_ops
from repro.kernels.slab_sweep.ops import sweep_vertices
from repro.kernels.slab_update import ops as update_ops
from repro.stream import sharded_store

V = 1 << 21            # Graph500 scale 21
CAPACITY = 1 << 22     # pow2 pool of one view of it (hashing=False)
# a 65,536-edge batch, 25% of it deletes: 49,152 inserts pad to 65,536
INS, INS_PADDED, DELS = 49152, 65536, 16384
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # the compiler logs nowhere
        from jax.experimental import topologies
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a persistent cache cannot read back entries for an absent chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield t
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def graph(one_chip):
    """Shapes of one scale-21 view, placed on one described chip."""
    g = jax.eval_shape(lambda: SG.empty(V, np.ones(V, np.int32), CAPACITY))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        g)


def _vec(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _fits_xla_only(lowered) -> str:
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, f"{need / 2 ** 30:.2f} GiB per device"
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    return text


def test_apply_epoch_compiles(graph, one_chip):
    """(a) The donated mixed update epoch (``apply_update``)."""
    ins = (_vec(INS_PADDED, jnp.uint32, one_chip),
           _vec(INS_PADDED, jnp.uint32, one_chip), None)
    dels = (_vec(DELS, jnp.uint32, one_chip),
            _vec(DELS, jnp.uint32, one_chip))
    _fits_xla_only(update_ops._apply_jit_don.lower(
        graph, ins, dels, impl="auto", interpret=None, queries_per_tile=256,
        use_commit_kernel=False))


@pytest.mark.parametrize("semiring,dtype", [("sum", jnp.float32),
                                            ("min", jnp.int32)])
def test_sweep_compiles(graph, one_chip, semiring, dtype):
    """(b) The pool sweep behind PageRank (sum) and label propagation
    (min)."""
    _fits_xla_only(jax.jit(
        lambda g, x: sweep_vertices(g, x, semiring=semiring)).lower(
            graph, _vec(V, dtype, one_chip)))


def test_compaction_rebuild_compiles(graph, one_chip):
    """(c) The compaction plan and the dense rebuild it feeds."""
    impl, interpret = compact_ops._resolve("auto", None)
    kw = dict(n_buckets=graph.n_buckets, impl=impl, interpret=interpret,
              rows_per_block=256, buckets_per_tile=256)
    _fits_xla_only(compact_ops._plan_jit.lower(
        graph.keys, graph.slab_vertex, graph.next_slab, **kw))
    plan = jax.eval_shape(
        lambda g: compact_ops._plan_body(g.keys, g.slab_vertex, g.next_slab,
                                         **kw), graph)
    plan = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), plan)
    _fits_xla_only(compact_ops._commit_jit.lower(
        graph, *plan, capacity_slabs=CAPACITY))


def test_sharded_epoch_compiles(topo):
    """(d) The single-program sharded epoch on four chips: forward,
    transpose and symmetric views, owner routing through an all-to-all."""
    S = 4
    mesh = Mesh(np.array(topo.devices[:S]), ("shard",),
                axis_types=(AxisType.Auto,))

    def view(capacity_per_shard):
        sg = jax.eval_shape(lambda: shard_empty(
            V, S, capacity_slabs_per_shard=capacity_per_shard))
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            sg.graphs, graph_pspecs(sg.graphs))

    # per shard: 2^19 local vertices; the symmetric view holds both
    # directions of every edge
    views = (view(CAPACITY // S), view(CAPACITY // S),
             view(2 * CAPACITY // S))
    vec = NamedSharding(mesh, P("shard"))
    dels = (_vec(DELS, jnp.uint32, vec), _vec(DELS, jnp.uint32, vec))
    ins = (_vec(INS_PADDED, jnp.uint32, vec),
           _vec(INS_PADDED, jnp.uint32, vec), None)

    # routing caps as the store sizes them for a uniform batch
    rng = np.random.default_rng(0)
    i_s, i_d = rng.integers(0, V, (2, INS)).astype(np.uint32)
    d_s, d_d = rng.integers(0, V, (2, DELS)).astype(np.uint32)

    def caps_of(a, padded):
        return (routing_cap_blocks(a, S, padded // S),
                sharded_store._cap_rung(max_owner_count(a, S)))

    def sym(a, b):
        return sharded_store._cap_rung(
            max_owner_count(np.concatenate([a, b]), S))

    caps = (caps_of(d_s, DELS), caps_of(d_d, DELS), sym(d_s, d_d),
            caps_of(i_s, INS_PADDED), caps_of(i_d, INS_PADDED), sym(i_s, i_d))
    text = _fits_xla_only(sharded_store._apply_sm_don.lower(
        views, dels, ins, roles=("forward", "transpose", "symmetric"),
        n_shards=S, caps=caps, mesh=mesh))
    assert "all-to-all" in text

"""ShardedSlabGraph — the paper's dynamic graph, vertex-partitioned across a
mesh (DESIGN.md §7: the sharded stream plane).

Partitioning: vertex v lives on shard ``v % n_shards``; its local id is
``v // n_shards`` (modulo striping balances power-law degree mass across
shards far better than contiguous blocks).  Every shard holds an independent
SlabGraph over its local vertices — stored src ids are LOCAL, stored dst
keys are GLOBAL (the update plane's dst guard is sentinel-based for exactly
this reason, DESIGN.md §6).  The pool arrays carry a leading shard dim that
is sharded over the mesh's batch-like axes; every per-shard operation runs
through the fused slab-update / slab-sweep engines ``vmap``-ed over that dim
— under pjit this compiles to pure shard-local compute, while the batch
ROUTING step (sort by owner + scatter into per-owner buckets) is the one
genuinely global exchange and lowers to the expected all-to-all pattern.

Routing overflow contract: ``route_edges`` buckets are fixed-``cap`` (shapes
are static under jit), so it also returns the number of edges the fullest
owner bucket could NOT place.  The ``*_edges_sharded`` entry points resolve
that on the host — ``cap=None`` defaults to the always-safe full batch
size, an explicit smaller ``cap`` is grown (pow2) and re-routed until every
edge lands.  Nothing is ever silently dropped.

Ops: batched insert/delete/query routing through the donated slab-update
engine, and distributed analytics on the slab-sweep engine — incremental
PageRank (sum sweeps; contrib reassembly = the one global exchange per
super-step), WCC (min-label sweeps over the symmetric sharded adjacency),
and BFS (unit min-plus sweeps with cross-shard frontier exchange).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..core import batch as B
from ..core import slab_graph as SG
from ..core.hashing import EMPTY_KEY, INVALID_SLAB, INVALID_VERTEX
from ..core.slab_graph import next_pow2
from ..kernels.slab_sweep.ops import sweep_vertices
from .collectives import exchange_buckets, gather_interleaved

UNREACHED = jnp.int32(2 ** 30)   # matches algorithms.bfs.UNREACHED

SHARD_AXIS = "shard"


@partial(jax.tree_util.register_dataclass,
         data_fields=["graphs"],
         meta_fields=["n_shards", "n_vertices_global", "mesh"])
@dataclasses.dataclass(frozen=True)
class ShardedSlabGraph:
    graphs: SG.SlabGraph          # every data leaf has leading dim n_shards
    n_shards: int
    n_vertices_global: int
    # the ("shard",) device mesh the stacked pools are pinned to, or None
    # when they live wherever jit put them.  Meta (not data): mesh presence
    # selects the shard_map single-program dispatch, so it must key jit
    # specialisation.
    mesh: Optional[Mesh] = None


def graph_pspecs(graphs: SG.SlabGraph):
    """Per-leaf ``P("shard", None, ...)`` specs for the stacked pools."""
    return jax.tree.map(
        lambda x: P(*((SHARD_AXIS,) + (None,) * (x.ndim - 1))), graphs)


def shard_mesh(n_shards: int) -> Mesh:
    """The 1-D ``("shard",)`` mesh over the first ``n_shards`` devices, one
    shard per device.  Raises when the backend has fewer devices: shards
    are never stacked onto one device behind the caller's back."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(
            f"{n_shards} shards need {n_shards} devices; the "
            f"{jax.default_backend()} backend has {len(devices)}")
    return jax.make_mesh((n_shards,), (SHARD_AXIS,),
                         axis_types=(AxisType.Auto,),
                         devices=devices[:n_shards])


def place_on_mesh(sg: ShardedSlabGraph, mesh: Mesh) -> ShardedSlabGraph:
    """Pin every stacked pool leaf under ``NamedSharding(P("shard", ...))``
    so per-shard state lives on its device for its whole lifetime
    (DESIGN.md §9).  The mesh must be 1-D, named ``("shard",)``, with one
    device per shard; after placement the shard_map single-program dispatch
    is auto-selected by the analytics and the sharded store."""
    if tuple(mesh.axis_names) != (SHARD_AXIS,):
        raise ValueError(f"expected a ('{SHARD_AXIS}',) mesh, got axes "
                         f"{tuple(mesh.axis_names)}")
    if any(t != AxisType.Auto for t in mesh.axis_types):
        # the plane's vmapped stacked-shard paths (query, vmap dispatch,
        # maintenance) mix mesh-placed pools with unplaced batches, which
        # only Auto (compiler-propagated) sharding accepts
        raise ValueError(f"expected an Auto-typed mesh (see shard_mesh), "
                         f"got axis types {mesh.axis_types}")
    if mesh.devices.size != sg.n_shards:
        raise ValueError(f"mesh has {mesh.devices.size} devices for "
                         f"{sg.n_shards} shards (need exactly one each)")
    specs = graph_pspecs(sg.graphs)
    graphs = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        sg.graphs, specs)
    return dataclasses.replace(sg, graphs=graphs, mesh=mesh)


def _resolve_dispatch(dispatch: str, mesh: Optional[Mesh]) -> str:
    if dispatch == "auto":
        return "shard_map" if mesh is not None else "vmap"
    if dispatch not in ("vmap", "shard_map"):
        raise ValueError(f"unknown dispatch {dispatch!r}")
    if dispatch == "shard_map" and mesh is None:
        raise ValueError("dispatch='shard_map' needs mesh-placed pools — "
                         "call place_on_mesh(sg, mesh) first")
    return dispatch


def shard_empty(n_vertices_global: int, n_shards: int, *,
                capacity_slabs_per_shard: int,
                weighted: bool = False) -> ShardedSlabGraph:
    n_local = -(-n_vertices_global // n_shards)
    g0 = SG.empty(n_local, np.ones(n_local, np.int32),
                  capacity_slabs_per_shard, weighted=weighted)
    graphs = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_shards,) + x.shape), g0)
    return ShardedSlabGraph(graphs=graphs, n_shards=n_shards,
                            n_vertices_global=n_vertices_global)


def shard_slice(sg: ShardedSlabGraph, k: int) -> SG.SlabGraph:
    """Shard ``k``'s local SlabGraph (host-side inspection / testing)."""
    return jax.tree.map(lambda x: x[k], sg.graphs)


def _grow_to(g: SG.SlabGraph, capacity: int) -> SG.SlabGraph:
    """Pad one shard's host (numpy) pools to an exact row count (stacking
    needs uniform shapes; unlike ``ensure_capacity`` this targets a
    capacity, not slack)."""
    grow = capacity - g.capacity_slabs
    if grow <= 0:
        return g

    def pad_rows(a, fill):
        pad = np.full((grow,) + a.shape[1:], fill, dtype=a.dtype)
        return np.concatenate([a, pad], axis=0)

    return dataclasses.replace(
        g,
        keys=pad_rows(g.keys, EMPTY_KEY),
        weights=pad_rows(g.weights, 0.0) if g.weighted else None,
        next_slab=pad_rows(g.next_slab, INVALID_SLAB),
        slab_vertex=pad_rows(g.slab_vertex, -1),
        free_list=pad_rows(g.free_list, INVALID_SLAB),
        slab_new=pad_rows(g.slab_new, False),
    )


def shard_from_edges_host(n_vertices_global: int, n_shards: int, src, dst,
                          weights=None, *, slack_slabs: int = 0,
                          mesh: Optional[Mesh] = None) -> ShardedSlabGraph:
    """Host-side bulk construction of the sharded graph (the compact
    ``from_edges_host`` analogue): partition edges by owner, build each
    shard's local pool densely (single-bucket mode, local src / GLOBAL dst
    keys), pad every pool to one common pow2 capacity, stack — all in host
    memory — then copy to the device, or with ``mesh`` straight to each
    shard's device (``place_on_mesh``).

    Semantically identical to routing the edges through
    ``insert_edges_sharded`` on ``shard_empty`` — without the engine's
    worst-case one-slab-per-lane capacity reservation, so pools come out
    sized to the edges actually stored (what every later O(pool) sweep
    pays for).
    """
    src = np.asarray(src, dtype=np.uint32)
    dst = np.asarray(dst, dtype=np.uint32)
    w = None if weights is None else np.asarray(weights, dtype=np.float32)
    n_local = -(-n_vertices_global // n_shards)
    shards = []
    for k in range(n_shards):
        m = (src % np.uint32(n_shards)) == k
        shards.append(SG.from_edges_numpy(
            n_local, src[m] // np.uint32(n_shards), dst[m],
            None if w is None else w[m],
            hashing=False, slack_slabs=slack_slabs))
    cap = next_pow2(max(g.capacity_slabs for g in shards))
    shards = [_grow_to(g, cap) for g in shards]
    graphs = jax.tree.map(lambda *xs: np.stack(xs), *shards)
    sg = ShardedSlabGraph(graphs=graphs, n_shards=n_shards,
                          n_vertices_global=n_vertices_global)
    if mesh is not None:
        return place_on_mesh(sg, mesh)
    return dataclasses.replace(sg, graphs=jax.tree.map(jnp.asarray, graphs))


def owner_of(v: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    return (v % jnp.uint32(n_shards)).astype(jnp.int32)


def local_id(v: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    return v // jnp.uint32(n_shards)


def global_id(local: jnp.ndarray, shard: jnp.ndarray,
              n_shards: int) -> jnp.ndarray:
    return local.astype(jnp.uint32) * jnp.uint32(n_shards) \
        + shard.astype(jnp.uint32)


def reassemble_global(x_local: jnp.ndarray, n_vertices_global: int
                      ) -> jnp.ndarray:
    """(n_shards, n_local) per-shard-local vector → (V,) global.

    Global id ``v = local * n_shards + shard``, so the shard axis interleaves:
    transpose to (n_local, n_shards), flatten, trim the tail padding of the
    last local row when ``V % n_shards != 0``.
    """
    return jnp.swapaxes(x_local, 0, 1).reshape(-1)[:n_vertices_global]


def ensure_capacity_sharded(sg: ShardedSlabGraph, extra_slabs: int, *,
                            high: Optional[int] = None) -> ShardedSlabGraph:
    """Host-side pool growth for the stacked pools (axis 1 = slab rows).

    Guarantees every shard has at least ``extra_slabs`` free slabs; grown
    capacities walk the same pow2 ladder as the unsharded
    ``ensure_capacity``.

    ``high`` is a host-known upper bound on the worst shard's allocated
    rows (max ``next_free``).  Passing it skips the blocking device read
    below — the sharded store tracks it with exact per-epoch insert
    accounting (the MaintenancePolicy O(1)-trigger trick), so steady-state
    epochs never sync on pool state.  ``None`` falls back to reading the
    device (one sync), using the tighter ``next_free - free_top`` headroom
    that credits recyclable slabs.
    """
    g = sg.graphs
    cap = g.keys.shape[1]
    if high is None:
        # worst-case shard: least bump headroom after its recyclables
        high = int(jnp.max(g.next_free - g.free_top))
    if cap - high >= extra_slabs:
        return sg
    target = max(high + extra_slabs, cap + cap // 2)
    grow = next_pow2(target) - cap

    def pad_rows(a, fill, dtype):
        pad = jnp.full((a.shape[0], grow) + a.shape[2:], fill, dtype=dtype)
        return jnp.concatenate([a, pad], axis=1)

    graphs = dataclasses.replace(
        g,
        keys=pad_rows(g.keys, EMPTY_KEY, jnp.uint32),
        weights=(pad_rows(g.weights, 0.0, jnp.float32)
                 if g.weighted else None),
        next_slab=pad_rows(g.next_slab, INVALID_SLAB, jnp.int32),
        slab_vertex=pad_rows(g.slab_vertex, -1, jnp.int32),
        free_list=pad_rows(g.free_list, INVALID_SLAB, jnp.int32),
        slab_new=pad_rows(g.slab_new, False, bool),
    )
    return dataclasses.replace(sg, graphs=graphs)


# ----------------------------------------------------------------------------
# owner routing — the one global exchange
# ----------------------------------------------------------------------------

def _route_body(src, dst, w, *, n_shards: int, cap: int):
    """Traced owner-routing body (also inlined by the sharded store's fused
    apply): (B,) global edges → (n_shards, cap) per-owner buckets."""
    valid = src != INVALID_VERTEX
    own = jnp.where(valid, owner_of(src, n_shards), n_shards)
    order = jnp.argsort(own, stable=True)
    so, ss, sd = own[order], src[order], dst[order]
    idx = jnp.arange(src.shape[0], dtype=jnp.int32)
    run_start = jnp.ones_like(so, dtype=bool).at[1:].set(so[1:] != so[:-1])
    base = jax.lax.cummax(jnp.where(run_start, idx, -1))
    rank = idx - base
    # true max per-owner run length — the overflow witness (initial=0:
    # an empty batch has no runs, not an undefined reduction)
    max_run = jnp.max(jnp.where(so < n_shards, rank + 1, 0), initial=0)
    overflow = jnp.maximum(max_run - cap, 0)
    ok = (so < n_shards) & (rank < cap)
    slot = jnp.where(ok, so * cap + rank, n_shards * cap)

    bsrc = jnp.full((n_shards * cap,), INVALID_VERTEX, jnp.uint32) \
        .at[slot].set(local_id(ss, n_shards), mode="drop")
    bdst = jnp.full((n_shards * cap,), INVALID_VERTEX, jnp.uint32) \
        .at[slot].set(sd, mode="drop")
    origin = jnp.full((n_shards * cap,), -1, jnp.int32) \
        .at[slot].set(order.astype(jnp.int32), mode="drop")
    bw = None
    if w is not None:
        bw = jnp.zeros((n_shards * cap,), jnp.float32) \
            .at[slot].set(w[order].astype(jnp.float32), mode="drop") \
            .reshape(n_shards, cap)
    return (bsrc.reshape(n_shards, cap), bdst.reshape(n_shards, cap), bw,
            origin.reshape(n_shards, cap), overflow)


@partial(jax.jit, static_argnames=("n_shards", "cap"))
def route_edges(src: jnp.ndarray, dst: jnp.ndarray,
                w: Optional[jnp.ndarray] = None, *, n_shards: int,
                cap: int):
    """Owner-routing: (B,) global edges → (n_shards, cap) per-owner buckets
    (src localised; INVALID padding; weights ride along when given).

    Returns ``(bsrc, bdst, bw, origin, overflow)``: ``origin`` maps bucket
    slots back to batch positions (-1 pad), ``bw`` is None when ``w`` is,
    and ``overflow`` is the number of edges beyond ``cap`` in the fullest
    owner bucket.  ``overflow > 0`` means the buckets are TOO SMALL and the
    unrouted edges are absent from them — callers must grow ``cap`` and
    re-route (the ``*_edges_sharded`` entry points do) rather than treat
    the buckets as complete.
    """
    return _route_body(src, dst, w, n_shards=n_shards, cap=cap)


def _pow2ceil(n: int) -> int:
    """Smallest power of two ≥ n, with a floor of 1 (``next_pow2``'s
    ``bit_length`` floor can never return 1, but an empty batch routes into
    a 1-wide bucket just fine)."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def routing_cap(src, n_shards: int) -> int:
    """Host-side exact bucket sizing: pow2 of the max per-owner edge count
    (pow2 quantization bounds the jit specialisations a batch stream sees)."""
    return _pow2ceil(max_owner_count(src, n_shards))


def max_owner_count(src, n_shards: int) -> int:
    """Host-side exact max per-owner edge count of a batch — sizes the vmap
    routing buckets AND bounds the worst shard's slab allocation for the
    store's host high-water accounting (worst case one slab per edge)."""
    src = np.asarray(src).astype(np.uint64)
    src = src[src != np.uint64(np.uint32(INVALID_VERTEX))]
    if src.size == 0:
        return 0
    counts = np.bincount((src % n_shards).astype(np.int64),
                         minlength=n_shards)
    return int(counts.max())


def routing_cap_blocks(src, n_shards: int, block: int) -> int:
    """Bucket sizing for the shard_map route: each source shard holds one
    contiguous ``block``-sized slice of the (padded) batch and buckets it
    per owner, so the cap bounds the max per-(source block, owner) PAIR
    count — typically ~1/S of the full-batch ``routing_cap``, which keeps
    the post-exchange engine batch (``n_shards * cap``) the same size as
    the vmap path's.  ``src`` is the UNPADDED host batch; the INVALID tail
    padding routes nowhere and cannot raise any pair count."""
    src = np.asarray(src).astype(np.uint64)
    valid = src != np.uint64(np.uint32(INVALID_VERTEX))
    if valid.size == 0 or block <= 0:
        return 1
    blk = np.arange(src.size) // block
    own = (src % n_shards).astype(np.int64)
    pair = blk * n_shards + own
    counts = np.bincount(pair[valid],
                         minlength=int(blk[-1] + 1) * n_shards)
    return _pow2ceil(int(counts.max(initial=0)))


def route_exchange(src, dst, w, *, n_shards: int, cap: int,
                   axis_name: str = SHARD_AXIS):
    """shard_map-local owner routing + all-to-all bucket exchange
    (DESIGN.md §9) — the single-program replacement for running
    ``_route_body`` replicated on the full batch.

    Runs INSIDE a shard_map body on this shard's (Bl,) contiguous slice of
    the global batch: buckets the local slice per owner (the same
    sort/scatter plan as ``_route_body``, at 1/S the size), then exchanges
    buckets so row ``i`` holds what source shard ``i`` routed here.
    Flattened, the (n_shards*cap,) engine batch lists this shard's edges in
    global batch order with INVALID padding at source-segment tails —
    interior padding, unlike the vmap path's tail-only padding, but the
    slab-update engine's plan is padding-position-independent (pads sort
    last, run planning sees only the valid prefix, scatters drop), so pool
    results stay leaf-for-leaf identical.

    Returns ``(bsrc, bdst, bw, origin, overflow)`` flattened to
    ``(n_shards*cap,)``; ``origin`` is in GLOBAL batch positions;
    ``overflow`` is the shard-max witness (pmax — replicated).
    """
    n_local = src.shape[0]
    me = jax.lax.axis_index(axis_name)
    bsrc, bdst, bw, origin, over = _route_body(src, dst, w,
                                               n_shards=n_shards, cap=cap)
    origin = jnp.where(origin >= 0, origin + me * n_local, -1)
    bsrc, bdst, origin = exchange_buckets((bsrc, bdst, origin), axis_name)
    if bw is not None:
        bw = exchange_buckets(bw, axis_name).reshape(-1)
    return (bsrc.reshape(-1), bdst.reshape(-1), bw, origin.reshape(-1),
            jax.lax.pmax(over, axis_name))


def _resolve_routing(sg: ShardedSlabGraph, src, dst, w, cap: Optional[int]):
    """Route with a guaranteed-complete cap.

    ``cap=None`` (and only None — ``cap=0`` is an explicit, growable size)
    defaults to the full batch length, which no owner bucket can exceed.
    Smaller explicit caps are checked against the routing's overflow
    witness on the host and grown (pow2) until every edge lands.
    """
    n = src.shape[0]
    if cap is None:
        cap = n
    # the loop is naturally bounded (cap >= n returns statically, pow2
    # growth reaches n in O(log n) retries) — the explicit budget turns a
    # logic regression or injected overflow storm into a structured error
    # instead of a spin
    attempts = 0
    max_attempts = max(4, n.bit_length() + 2)
    while True:
        bsrc, bdst, bw, origin, overflow = route_edges(
            src, dst, w, n_shards=sg.n_shards, cap=cap)
        if cap >= n:        # statically safe — no host sync, trace-friendly
            return bsrc, bdst, bw, origin
        if isinstance(overflow, jax.core.Tracer):
            raise ValueError(
                "insert/delete/query_edges_sharded traced with cap "
                f"{cap} < batch {n}: overflow cannot be checked inside "
                "jit — pass cap=None (safe default) or cap >= batch size")
        from ..resilience import faults
        over = int(overflow) + faults.fault_overflow(
            "route.resolve", cap=cap, n=n)
        if over == 0:
            return bsrc, bdst, bw, origin
        attempts += 1
        if attempts >= max_attempts:
            from ..resilience.guard import RetryExhausted
            raise RetryExhausted(
                "route.resolve", attempts,
                RuntimeError(f"routing still overflows at cap {cap} "
                             f"(batch {n}, overflow {over})"))
        new_cap = min(next_pow2(cap + over, lo=1), n)
        from .. import obs
        obs.instant("route.grow_retry", cap=cap, over=over,
                    new_cap=new_cap)
        obs.emit_event("route_grow_retry", cap=cap, overflow=over,
                       new_cap=new_cap)
        obs.inc("route.grow_retry")
        cap = new_cap


def _scatter_back(mask: jnp.ndarray, origin: jnp.ndarray,
                  n: int) -> jnp.ndarray:
    """(n_shards, cap) per-slot results → (B,) batch-aligned results."""
    return jnp.zeros((n,), bool).at[
        jnp.where(origin >= 0, origin, n).reshape(-1)
    ].set(mask.reshape(-1), mode="drop")


# ----------------------------------------------------------------------------
# batched mutation through the fused engine
# ----------------------------------------------------------------------------

def insert_edges_sharded(sg: ShardedSlabGraph, src: jnp.ndarray,
                         dst: jnp.ndarray, w: Optional[jnp.ndarray] = None,
                         *, cap: Optional[int] = None, donate: bool = False
                         ) -> Tuple[ShardedSlabGraph, jnp.ndarray]:
    """Batched insert across shards: one owner-routing exchange + one
    engine dispatch (``update_shards``).  ``cap`` bounds per-shard batch
    size (None = full batch, always safe; smaller caps grow on overflow —
    no edge is ever dropped).  ``donate=True`` mutates the pools in place.
    """
    if src.shape[0] == 0:
        return sg, jnp.zeros((0,), bool)
    bsrc, bdst, bw, origin = _resolve_routing(sg, src, dst, w, cap)
    graphs, ins, _ = B.update_shards(sg.graphs, ins=(bsrc, bdst, bw),
                                     donate=donate)
    return (dataclasses.replace(sg, graphs=graphs),
            _scatter_back(ins, origin, src.shape[0]))


def delete_edges_sharded(sg: ShardedSlabGraph, src: jnp.ndarray,
                         dst: jnp.ndarray, *, cap: Optional[int] = None,
                         donate: bool = False
                         ) -> Tuple[ShardedSlabGraph, jnp.ndarray]:
    if src.shape[0] == 0:
        return sg, jnp.zeros((0,), bool)
    bsrc, bdst, _, origin = _resolve_routing(sg, src, dst, None, cap)
    graphs, _, dele = B.update_shards(sg.graphs, dels=(bsrc, bdst),
                                      donate=donate)
    return (dataclasses.replace(sg, graphs=graphs),
            _scatter_back(dele, origin, src.shape[0]))


def query_edges_sharded(sg: ShardedSlabGraph, src: jnp.ndarray,
                        dst: jnp.ndarray, *, cap: Optional[int] = None
                        ) -> jnp.ndarray:
    if src.shape[0] == 0:
        return jnp.zeros((0,), bool)
    bsrc, bdst, _, origin = _resolve_routing(sg, src, dst, None, cap)
    found = B.query_shards(sg.graphs, bsrc, bdst)
    return _scatter_back(found, origin, src.shape[0])


def apply_update_sharded(sg: ShardedSlabGraph, ins_src=None, ins_dst=None,
                         ins_w=None, del_src=None, del_dst=None, *,
                         cap: Optional[int] = None, donate: bool = True
                         ) -> Tuple[ShardedSlabGraph,
                                    Optional[jnp.ndarray],
                                    Optional[jnp.ndarray]]:
    """One mixed epoch (deletes before inserts) in ONE engine dispatch:
    both halves are routed, then ``update_shards`` applies them fused with
    the stacked pools donated — the sharded analogue of ``apply_update``.
    """
    ins = dels = None
    ins_origin = del_origin = None
    if del_src is not None and del_src.shape[0] > 0:
        ds, dd, _, del_origin = _resolve_routing(sg, del_src, del_dst,
                                                 None, cap)
        dels = (ds, dd)
    if ins_src is not None and ins_src.shape[0] > 0:
        is_, id_, iw, ins_origin = _resolve_routing(sg, ins_src, ins_dst,
                                                    ins_w, cap)
        ins = (is_, id_, iw)
    if ins is None and dels is None:
        return sg, None, None
    graphs, ins_m, del_m = B.update_shards(sg.graphs, ins=ins, dels=dels,
                                           donate=donate)
    sg = dataclasses.replace(sg, graphs=graphs)
    ins_mask = (None if ins_m is None
                else _scatter_back(ins_m, ins_origin, ins_src.shape[0]))
    del_mask = (None if del_m is None
                else _scatter_back(del_m, del_origin, del_src.shape[0]))
    return sg, ins_mask, del_mask


# ----------------------------------------------------------------------------
# distributed analytics on the slab-sweep engine
# ----------------------------------------------------------------------------
#
# Each algorithm is one fixpoint loop over "global sweep" super-steps.  The
# loop math is shared between dispatch modes so they stay bit-identical:
#
#   * dispatch="vmap"      — the engine sweep vmapped over the stacked shard
#     dim; the exchange is a ``reassemble_global`` reshape.  Runs anywhere
#     (the bit-exact fallback).
#   * dispatch="shard_map" — ONE shard_map program over the ("shard",) mesh:
#     the whole while_loop runs per shard (SPMD — every shard computes the
#     replicated convergence state identically), the exchange is an
#     ``all_gather`` over the shard axis, and each shard returns only its
#     strided slice of the result.  Needs mesh-placed pools
#     (``place_on_mesh``).
#   * dispatch="auto"      — shard_map iff ``sg.mesh`` is set.
#
# ``rows`` statically bounds every sweep to the allocated pool prefix
# (bit-identical — see ``slab_sweep.ops``); the sharded store supplies it
# from host high-water accounting so sweeps never pay for pow2 slack.

def _pagerank_fix(sums_local_of, V, pr0, out_degree, damping, error_margin,
                  max_iter, slice_local, exchange):
    """The PageRank fixpoint with owned-slice vector math — shared by both
    dispatch modes so their per-super-step math is bit-identical.

    The elementwise update (contrib, rank refresh) runs on each shard's
    owned ``(n_local,)`` slice (stacked under vmap), so the per-super-step
    O(V) elementwise work drops to O(V / n_shards) per shard instead of
    being replicated on every shard.  Only the replicated global
    reductions (teleport mass, L1 delta) read the exchanged ``(V,)``
    vectors — identical arrays in both modes, so nothing regroups and the
    modes stay bit-identical (and the values stay elementwise-identical to
    the replicated form this replaces)."""
    zero_out = out_degree == 0
    has_sink = jnp.any(zero_out)
    deg_loc = slice_local(out_degree)
    base = (1.0 - damping) / V

    def body(carry):
        pr, _, it = carry
        pr_loc = slice_local(pr)
        contrib = exchange(jnp.where(deg_loc > 0,
                                     pr_loc / jnp.maximum(deg_loc, 1), 0.0))
        new_loc = base + damping * sums_local_of(contrib)
        teleport = jnp.sum(jnp.where(zero_out, pr, 0.0)) / V
        new_loc = jnp.where(has_sink, new_loc + damping * teleport, new_loc)
        new_pr = exchange(new_loc)
        delta = jnp.sum(jnp.abs(new_pr - pr))
        return new_pr, delta, it + 1

    def cond(carry):
        _, delta, it = carry
        return (delta > error_margin) & (it < max_iter)

    return jax.lax.while_loop(
        cond, body, (pr0, jnp.asarray(jnp.inf, jnp.float32),
                     jnp.asarray(0, jnp.int32)))


def _minfix(min_of, x0, changed0, max_iters):
    """Frontier-masked monotone-min fixpoint (WCC labels / BFS levels)."""
    def cond(carry):
        _, changed, it = carry
        return jnp.any(changed) & (it < max_iters)

    def body(carry):
        x, changed, it = carry
        new = jnp.minimum(x, min_of(x, changed))
        return new, new < x, it + 1

    return jax.lax.while_loop(
        cond, body, (x0, changed0, jnp.asarray(0, jnp.int32)))


def _local_slice_idx(V: int, n_shards: int, me) -> jnp.ndarray:
    """Global ids owned by shard ``me`` (strided; tail clamped — the clamp
    positions land past V after reassembly and are trimmed)."""
    n_local = -(-V // n_shards)
    return jnp.minimum(jnp.arange(n_local) * n_shards + me, V - 1)


def _run_sharded_fix(sg: ShardedSlabGraph, dispatch, rows, fix_of, consts):
    """Dispatch one analytics fixpoint.

    ``fix_of(sweep, exchange, slice_local, *consts)`` must return the
    while_loop carry where element 0 is the (V,) result and element 2 the
    iteration counter; ``sweep(values, frontier, kw)`` is per-shard-local,
    ``exchange`` lifts the per-shard local vector(s) to the (V,) global
    one, and ``slice_local`` is its inverse — the owned strided slice of a
    replicated (V,) vector (stacked (S, n_local) under vmap), for fixpoints
    that keep their elementwise math per shard.  ``consts`` are the traced
    global vectors the fixpoint reads — passed as explicit replicated
    shard_map inputs (bodies cannot close over tracers).
    """
    V, S = sg.n_vertices_global, sg.n_shards
    dispatch = _resolve_dispatch(dispatch, sg.mesh)

    if dispatch == "vmap":
        idx_all = jnp.stack([_local_slice_idx(V, S, s) for s in range(S)])

        def exchange(x_stacked):
            return reassemble_global(x_stacked, V)

        def slice_local(x_glob):
            return x_glob[idx_all]

        def sweep(values, frontier, sweep_kw):
            return jax.vmap(lambda g: sweep_vertices(
                g, values, frontier=frontier, n_keys=V, rows=rows,
                **sweep_kw))(sg.graphs)
        out = fix_of(sweep, exchange, slice_local, *consts)
        return out[0], out[2]

    def body_shard(graphs_blk, *consts_in):
        g = jax.tree.map(lambda x: x[0], graphs_blk)
        me = jax.lax.axis_index(SHARD_AXIS)

        def exchange(x_local):
            return gather_interleaved(x_local, V, SHARD_AXIS)

        def slice_local(x_glob):
            return x_glob[_local_slice_idx(V, S, me)]

        def sweep(values, frontier, sweep_kw):
            return sweep_vertices(g, values, frontier=frontier, n_keys=V,
                                  rows=rows, **sweep_kw)
        out = fix_of(sweep, exchange, slice_local, *consts_in)
        # every shard holds the identical replicated result; emit only the
        # strided slice this shard owns (+ its copy of the iter counter)
        return out[0][_local_slice_idx(V, S, me)][None], out[2][None]

    res_loc, iters = jax.shard_map(
        body_shard, mesh=sg.mesh,
        in_specs=(graph_pspecs(sg.graphs),) + tuple(P() for _ in consts),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS)),
        check_vma=False)(sg.graphs, *consts)
    return reassemble_global(res_loc, V), iters[0]


@partial(jax.jit, static_argnames=("damping", "max_iter", "impl", "rows",
                                   "dispatch"))
def pagerank_sharded(sg_in: ShardedSlabGraph, out_degree: jnp.ndarray, *,
                     init_pr: Optional[jnp.ndarray] = None,
                     damping: float = 0.85, error_margin: float = 1e-5,
                     max_iter: int = 100, impl: str = "auto",
                     rows: Optional[int] = None, dispatch: str = "auto"
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed PageRank over the IN-edge sharded graph.

    Per super-step each shard runs ONE slab-sweep engine sum sweep
    (global-key bound ``n_keys=V``); the only cross-shard traffic is the
    reassembly of the global contrib vector ((V,) f32 — an all_gather over
    the shard axis under ``dispatch="shard_map"``, a stacked reshape under
    ``"vmap"``; bit-identical either way).  ``out_degree`` is the GLOBAL
    out-degree vector; ``rows`` statically bounds the sweeps to the
    allocated pool prefix.
    """
    V = sg_in.n_vertices_global
    pr0 = (jnp.full((V,), 1.0 / V, jnp.float32) if init_pr is None
           else init_pr.astype(jnp.float32))

    def fix_of(sweep, exchange, slice_local, pr0, out_degree):
        def sums_local_of(contrib):
            return sweep(contrib, None, dict(semiring="sum", impl=impl))
        return _pagerank_fix(sums_local_of, V, pr0, out_degree, damping,
                             error_margin, max_iter, slice_local, exchange)

    return _run_sharded_fix(sg_in, dispatch, rows, fix_of,
                            (pr0, out_degree))


@partial(jax.jit, static_argnames=("max_iters", "impl", "rows", "dispatch"))
def wcc_sharded(sg_sym: ShardedSlabGraph, *,
                init_labels: Optional[jnp.ndarray] = None,
                max_iters: int = 100000, impl: str = "auto",
                rows: Optional[int] = None, dispatch: str = "auto"
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed WCC: frontier-masked min-label sweeps over the SYMMETRIC
    sharded adjacency to a fixpoint.  Integer min is exact, so the labels
    (min vertex id per component) are bit-identical to
    ``wcc_labelprop_sweep`` on the unsharded union — and between dispatch
    modes.  ``init_labels`` warm starts insert-only incremental runs
    (labels only ever decrease).
    """
    V = sg_sym.n_vertices_global
    labels0 = (jnp.arange(V, dtype=jnp.int32) if init_labels is None
               else init_labels.astype(jnp.int32))

    def fix_of(sweep, exchange, _slice, labels0):
        def min_of(labels, changed):
            return exchange(sweep(labels, changed, dict(semiring="min",
                                                        impl=impl)))
        return _minfix(min_of, labels0, jnp.ones((V,), bool), max_iters)

    return _run_sharded_fix(sg_sym, dispatch, rows, fix_of, (labels0,))


@partial(jax.jit, static_argnames=("src", "max_iters", "impl", "rows",
                                   "dispatch"))
def bfs_sharded(sg_in: ShardedSlabGraph, *, src: int,
                init_dist: Optional[jnp.ndarray] = None,
                max_iters: int = 100000, impl: str = "auto",
                rows: Optional[int] = None, dispatch: str = "auto"
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed level-synchronous BFS over the IN-edge sharded graph.

    Per super-step each shard relaxes with ONE unit-weight min-plus sweep
    masked to the changed frontier; the exchanged global distance vector IS
    the cross-shard frontier exchange.  Distances are integer levels
    (UNREACHED = 2^30), bit-identical to ``bfs_vanilla`` on the unsharded
    union and between dispatch modes.  ``init_dist`` warm starts
    insert-only incremental runs (valid upper bounds only ever decrease
    under Bellman-Ford).
    """
    V = sg_in.n_vertices_global
    if init_dist is None:
        dist0 = jnp.full((V,), UNREACHED, jnp.int32).at[src].set(0)
        changed0 = jnp.zeros((V,), bool).at[src].set(True)
    else:
        dist0 = init_dist.astype(jnp.int32).at[src].set(0)
        changed0 = dist0 < UNREACHED

    def fix_of(sweep, exchange, _slice, dist0, changed0):
        def min_of(dist, changed):
            return exchange(sweep(dist, changed, dict(semiring="min_plus",
                                                      impl=impl)))
        return _minfix(min_of, dist0, changed0, max_iters)

    return _run_sharded_fix(sg_in, dispatch, rows, fix_of,
                            (dist0, changed0))


# ----------------------------------------------------------------------------
# Distributed triangle counting (slab_intersect family, Alg. 9)
# ----------------------------------------------------------------------------
# 6T = Σ_k Σ_j Count(shard_j, shard_k, { (u,v) on shard k : owner(u) = j }):
# candidate enumeration N(v) is shard-local on owner(v) = k (stored src ids
# are local, stored dst keys global — exactly what the intersect kernel's G2
# walk needs), while the (u,w) membership probe resolves entirely on
# owner(u) = j because u's whole adjacency lives there.  The S rotations of
# the stacked pools realise the Σ_j as the systolic all-to-all idiom; each
# rotation is ONE vmapped count over every shard, and the final Σ_k is the
# single collective reduction.

def _compact_shard_edges(srcf, dstf, okf, *, cap: int):
    """Per-shard prefix-sum edge compaction (flattened pool lanes)."""
    m = okf.astype(jnp.int32)
    pos = jnp.cumsum(m) - m
    idx = jnp.where(okf & (pos < cap), pos, cap)
    es = jnp.zeros((cap,), jnp.uint32).at[idx].set(
        srcf.astype(jnp.uint32), mode="drop")
    ed = jnp.zeros((cap,), jnp.uint32).at[idx].set(dstf, mode="drop")
    return es, ed, jnp.minimum(jnp.sum(m), cap)


@partial(jax.jit, static_argnames=("impl", "interpret", "max_bpv", "cap"))
def _triangle_counts_sharded(graphs, *, impl: str, interpret: bool,
                             max_bpv: int, cap: int) -> jnp.ndarray:
    from ..core.worklist import pool_edges
    from ..kernels.slab_intersect.ops import count_edges_local
    S = graphs.keys.shape[0]
    view = jax.vmap(pool_edges)(graphs)
    es, ed, n = jax.vmap(partial(_compact_shard_edges, cap=cap))(
        view.src.reshape(S, -1), view.dst.reshape(S, -1),
        view.valid.reshape(S, -1))
    emask = jnp.arange(cap)[None, :] < n[:, None]
    owner = (ed % jnp.uint32(S)).astype(jnp.int32)
    u_local = ed // jnp.uint32(S)
    shard_ids = jnp.arange(S, dtype=jnp.int32)[:, None]
    vcount = jax.vmap(partial(count_edges_local, impl=impl,
                              interpret=interpret, max_bpv=max_bpv,
                              lane_chunk=32, edges_per_tile=8))
    total = jnp.zeros((S,), jnp.int32)
    for r in range(S):
        g1 = jax.tree.map(lambda x: jnp.roll(x, -r, axis=0), graphs)
        m = emask & (owner == (shard_ids + r) % S)
        total = total + vcount(g1, graphs, u_local, es, m)
    return total


def triangles_sharded(sg_sym: ShardedSlabGraph, *, impl: str = "auto",
                      interpret: Optional[bool] = None,
                      max_bpv: Optional[int] = None,
                      cap: Optional[int] = None) -> jnp.ndarray:
    """Global triangle count over the SYMMETRIC sharded view.

    Bit-identical to ``algorithms.triangles_static`` on the unsharded union
    (integer sums, order-free).  ``cap`` bounds the per-shard compacted edge
    set and defaults to the exact worst-shard live-lane count (pow2), so it
    never overflows; ``max_bpv`` defaults to the pow2-rounded worst bucket
    count across shards.
    """
    from ..kernels.slab_intersect.ops import _resolve
    impl, interpret = _resolve(impl, interpret)
    graphs = sg_sym.graphs
    S = sg_sym.n_shards
    if max_bpv is None:
        max_bpv = next_pow2(int(jnp.max(graphs.bucket_count)), lo=1)
    if cap is None:
        from ..core.worklist import pool_edges
        valid = jax.vmap(lambda g: pool_edges(g).valid)(graphs)
        cap = next_pow2(int(jnp.max(jnp.sum(
            valid.reshape(S, -1).astype(jnp.int32), axis=1))), lo=128)
    counts = _triangle_counts_sharded(graphs, impl=impl, interpret=interpret,
                                      max_bpv=max_bpv, cap=cap)
    return jnp.sum(counts) // 6

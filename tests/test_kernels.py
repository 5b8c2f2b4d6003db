"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.slab_pagerank.kernel import slab_contrib_sums_pallas
from repro.kernels.slab_pagerank.ref import slab_contrib_sums_ref
from repro.kernels.slab_intersect.kernel import probe_hits_pallas
from repro.kernels.slab_intersect.ref import probe_hits_ref
from repro.kernels.slab_intersect.ops import search_edges_kernel
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # (B, Hq, Hkv, Sq, Skv, D, causal, window, softcap, dtype)
    (1, 4, 4, 128, 128, 64, True, 0, 0.0, jnp.float32),
    (2, 4, 2, 256, 256, 64, True, 0, 0.0, jnp.float32),     # GQA
    (1, 4, 1, 128, 128, 64, True, 0, 0.0, jnp.float32),     # MQA
    (1, 2, 2, 256, 256, 64, True, 64, 0.0, jnp.float32),    # sliding window
    (1, 2, 2, 128, 128, 64, True, 0, 30.0, jnp.float32),    # softcap (gemma2)
    (1, 2, 2, 128, 128, 64, False, 0, 0.0, jnp.float32),    # bidirectional
    (1, 2, 1, 128, 256, 128, True, 0, 0.0, jnp.bfloat16),   # bf16, d=128
    (1, 4, 2, 256, 256, 64, True, 128, 50.0, jnp.float32),  # window+softcap
]


@pytest.mark.parametrize("case", ATTN_CASES,
                         ids=[f"attn{i}" for i in range(len(ATTN_CASES))])
def test_flash_attention_matches_ref(case):
    B, Hq, Hkv, Sq, Skv, D, causal, window, softcap, dtype = case
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_q=64, block_k=64,
                          interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window,
                         softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_kv_len_mask():
    """Decode-style padded KV cache."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    got = flash_attention(q, k, v, causal=False, kv_len=130, block_q=64,
                          block_k=64, interpret=True)
    want = attention_ref(q, k, v, causal=False, kv_len=130)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_block_shapes():
    """Block sweep: result independent of tiling."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    want = attention_ref(q, k, v, causal=True)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5, err_msg=f"{bq}x{bk}")


# ---------------------------------------------------------------------------
# slab_pagerank
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("S,V,R", [(16, 100, 8), (100, 1000, 32),
                                   (257, 50, 64), (512, 4096, 256)])
def test_slab_pagerank_sweep(S, V, R):
    rng = np.random.default_rng(3)
    keys = rng.integers(0, V, (S, 128)).astype(np.uint32)
    # sprinkle sentinels + unallocated rows
    keys[rng.random((S, 128)) < 0.3] = 0xFFFFFFFE
    keys[rng.random((S, 128)) < 0.1] = 0xFFFFFFFD
    owner = rng.integers(-1, 50, S).astype(np.int32)
    contrib = rng.standard_normal(V).astype(np.float32)
    got = slab_contrib_sums_pallas(jnp.asarray(keys), jnp.asarray(owner),
                                   jnp.asarray(contrib), n_vertices=V,
                                   rows_per_block=R, interpret=True)
    want = slab_contrib_sums_ref(jnp.asarray(keys), jnp.asarray(owner),
                                 jnp.asarray(contrib), n_vertices=V)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-5)


def test_slab_pagerank_in_pagerank():
    """End-to-end: pagerank(contrib_impl='pallas') == pagerank(ref)."""
    from repro.core import from_edges_host
    from repro.algorithms import pagerank
    rng = np.random.default_rng(4)
    n = 50
    src = rng.integers(0, n, 250).astype(np.uint32)
    dst = rng.integers(0, n, 250).astype(np.uint32)
    g_in = from_edges_host(n, dst, src, hashing=False)
    out_deg = np.bincount(src, minlength=n)
    # dedup-consistent out-degree
    uniq = set(zip(src.tolist(), dst.tolist()))
    out_deg = np.zeros(n, np.int32)
    for s, _ in uniq:
        out_deg[s] += 1
    pr_ref, _ = pagerank(g_in, jnp.asarray(out_deg), contrib_impl="ref")
    pr_pal, _ = pagerank(g_in, jnp.asarray(out_deg), contrib_impl="pallas")
    np.testing.assert_allclose(np.asarray(pr_pal), np.asarray(pr_ref),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# slab_intersect
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("Q,C,S", [(8, 2, 16), (300, 4, 64), (1024, 8, 256)])
def test_slab_intersect_sweep(Q, C, S):
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 1000, (S, 128)).astype(np.uint32)
    ws = rng.integers(0, 1000, Q).astype(np.uint32)
    rows = rng.integers(-1, S, (Q, C)).astype(np.int32)
    got = probe_hits_pallas(jnp.asarray(ws), jnp.asarray(rows),
                            jnp.asarray(keys), queries_per_block=128,
                            interpret=True)
    want = probe_hits_ref(jnp.asarray(ws), jnp.asarray(rows),
                          jnp.asarray(keys))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_search_edges_kernel_matches_algorithm():
    """Kernel path == algorithm-layer chain probe on a real graph."""
    from repro.core import from_edges_host
    from repro.algorithms import search_edges
    rng = np.random.default_rng(6)
    n = 64
    src = rng.integers(0, n, 400).astype(np.uint32)
    dst = rng.integers(0, n, 400).astype(np.uint32)
    g = from_edges_host(n, src, dst, hashing=True)
    qs = rng.integers(0, n, 128).astype(np.uint32)
    qd = rng.integers(0, n, 128).astype(np.uint32)
    mask = jnp.ones(128, bool)
    want = search_edges(g, jnp.asarray(qs), jnp.asarray(qd), mask)
    for impl in ("auto", "pallas"):
        got = search_edges_kernel(g, jnp.asarray(qs), jnp.asarray(qd), mask,
                                  max_chain=8, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,L,N,D,dtype", [
    (8, 4, 100, 32, jnp.float32),
    (64, 16, 1000, 64, jnp.float32),
    (100, 8, 500, 128, jnp.float32),
    (32, 8, 256, 64, jnp.bfloat16),
])
def test_embedding_bag_sweep(B, L, N, D, dtype):
    rng = np.random.default_rng(7)
    idx = rng.integers(0, N, (B, L)).astype(np.int32)
    idx[rng.random((B, L)) < 0.2] = -1  # ragged bags
    w = rng.standard_normal((B, L)).astype(np.float32)
    table = jnp.asarray(rng.standard_normal((N, D)), dtype)
    got = embedding_bag_pallas(jnp.asarray(idx), jnp.asarray(w), table,
                               bags_per_block=32, interpret=True)
    want = embedding_bag_ref(jnp.asarray(idx), jnp.asarray(w), table)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


# ---------------------------------------------------------------------------
# chunked (flash-schedule XLA) attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", [
    (1, 4, 2, 256, 256, 64, True, 0, 0.0),
    (2, 4, 1, 128, 256, 64, False, 0, 0.0),
    (1, 2, 2, 256, 256, 32, True, 64, 30.0),
    (1, 8, 8, 128, 128, 128, True, 0, 50.0),
])
def test_chunked_attention_matches_ref(case):
    from repro.kernels.flash_attention.chunked import attention_chunked
    B, Hq, Hkv, Sq, Skv, D, causal, window, cap = case
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.standard_normal((B, Hq, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Skv, D)), jnp.float32)
    for bk in (64, 128):
        got = attention_chunked(q, k, v, causal=causal, window=window,
                                softcap=cap, block_k=bk)
        want = attention_ref(q, k, v, causal=causal, window=window,
                             softcap=cap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_chunked_attention_grad_matches_ref():
    from repro.kernels.flash_attention.chunked import attention_chunked
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 32)), jnp.float32)
    g1 = jax.grad(lambda q: attention_chunked(q, k, v, block_k=64).sum())(q)
    g2 = jax.grad(lambda q: attention_ref(q, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# implementation selection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_auto_resolves_to_the_xla_engine(monkeypatch, backend):
    """``impl="auto"`` never picks a Pallas kernel, on any backend: the
    TPU v5e compiler refuses them, and the CPU tests must run the path the
    chip runs."""
    import jax
    from repro.kernels.slab_compact.ops import _resolve as compact
    from repro.kernels.slab_intersect.ops import _resolve as intersect
    from repro.kernels.slab_sweep.ops import _resolve as sweep
    from repro.kernels.slab_update.ops import _resolve as update
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sweep("auto", None) == ("ref", backend != "tpu")
    for resolve in (update, compact, intersect):
        assert resolve("auto", None) == ("jnp", backend != "tpu")
    # Pallas stays reachable by name: interpreted off-TPU, compiled on it
    assert update("pallas", None) == ("pallas", backend != "tpu")


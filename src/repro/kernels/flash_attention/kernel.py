"""Blocked online-softmax attention (FlashAttention) as a Pallas TPU kernel.

Supports the whole assigned LM pool from one kernel:
  * GQA / MQA         — kv head = q head // group (gemma-2b MQA, GQA elsewhere)
  * causal masking    — training / prefill
  * sliding window    — gemma2-9b local layers (causal window)
  * logit soft-capping— gemma2-9b (s ← cap·tanh(s/cap))
  * kv_len masking    — padded decode caches

Tiling: grid = (batch, q_heads, Sq/bq, Skv/bk); the innermost grid dimension
is the softmax reduction, carried in VMEM scratch (acc, m, l) — the canonical
TPU flash schedule.  Q/K/V tiles are (bq, d) / (bk, d) VMEM blocks; d is kept
whole (128/256 for this pool — MXU-aligned).  Fully-masked K blocks are
skipped with ``pl.when`` (the causal lower-left / window band).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 sm_scale: float, causal: bool, window: int, softcap: float,
                 kv_len: int, block_q: int, block_k: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = i * block_q
    k0 = j * block_k
    # block-level skip: in a causal/windowed schedule most (i, j) tiles are
    # entirely outside the band — do not touch the MXU for them.
    needed = k0 < kv_len
    if causal:
        needed &= (q0 + block_q - 1) >= k0
    if window > 0:
        # causal sliding window: q attends to [q - window + 1, q]
        needed &= (q0 - (k0 + block_k - 1)) < window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        qi = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kj = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kj < kv_len
        if causal:
            mask &= qi >= kj
        if window > 0:
            mask &= (qi - kj) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "sm_scale",
                              "kv_len", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, sm_scale: float | None = None,
                    kv_len: int | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = D ** -0.5
    if kv_len is None:
        kv_len = Skv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)

    grid = (B, Hq, Sq // bq, Skv // bk)
    kern = functools.partial(
        _attn_kernel, sm_scale=float(sm_scale), causal=causal,
        window=int(window), softcap=float(softcap), kv_len=int(kv_len),
        block_q=bq, block_k=bk)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)

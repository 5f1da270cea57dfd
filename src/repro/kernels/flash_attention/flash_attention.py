"""Pallas TPU kernel: flash attention with logit softcap + sliding window.

Covers the attention variants the assigned archs need (gemma2 local+
global with softcap, danube SWA, plain GQA): online-softmax over KV
blocks with m/l/acc carries in VMEM scratch, fp32 accumulation.

Grid: (batch*q_heads, Sq/bq, Skv/bk) with the KV dimension innermost
("arbitrary") so the carries live across kv steps.  GQA is handled by
indexing the KV head = q_head // group_size in the BlockSpec index maps
(no materialized head repetition).  Causal/window masks are applied
per-block; fully-masked blocks still iterate but contribute zeros — the
block-skipping refinement (shrinking the kv grid per q block) is a
documented perf follow-up, not a correctness issue.

head_dim is padded to a multiple of 128 by ops.py (danube hd=120).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, logit_cap, causal, window, bq, bk, kv_steps, sq, skv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap > 0.0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (skv - sq)                                  # align decode offsets
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < skv          # true (pre-padding) kv length
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked block: s == m_new == NEG_INF would give exp(0)=1 —
    # force masked probabilities to exactly zero.
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, scale: float | None = None,
                    bq: int = 256, bk: int = 256, interpret: bool = False,
                    true_sq: int | None = None, true_skv: int | None = None):
    """q: (B, H, Sq, hd); k,v: (B, KV, Skv, hd) -> (B, H, Sq, hd).

    hd must be a multiple of 128 and Sq/Skv multiples of bq/bk (ops.py
    pads; true_sq/true_skv are the pre-padding lengths for masking).
    GQA via H = g * KV."""
    b, h, sq, hd = q.shape
    _, kv, skv, _ = k.shape
    assert h % kv == 0
    group = h // kv
    scale = hd ** -0.5 if scale is None else scale
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    kv_steps = skv // bk
    kernel = functools.partial(
        _kernel, scale=scale, logit_cap=logit_cap, causal=causal,
        window=window, bq=bq, bk=bk, kv_steps=kv_steps,
        sq=true_sq or sq, skv=true_skv or skv)
    return pl.pallas_call(
        kernel,
        grid=(b * h, sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda bh, qi, ki: (bh // h, bh % h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bh, qi, ki: (bh // h, (bh % h) // group,
                                             ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bh, qi, ki: (bh // h, (bh % h) // group,
                                             ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bh, qi, ki: (bh // h, bh % h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)

"""Pallas TPU kernel: paged gather-attention for single-token decode.

Decode attention against a paged KV cache (DESIGN.md §11): K/V live in a
(num_blocks, block_size, KV, hd) pool and each batch row reads its keys
through a (pages,) slice of the block table.  The table and per-row
cache lengths arrive as *scalar-prefetched* operands — the K/V BlockSpec
index maps dereference ``bt_ref`` to pick the physical block for each
(row, page) grid step, so the kernel streams exactly the pages a row
owns and never materialises the gathered (B, P*bs, KV, hd) view the XLA
path builds.

Grid: (batch, pages) with the page dimension innermost ("arbitrary") so
the online-softmax m/l/acc carries live across pages.  Each grid step
covers every head of its row: q is blocked (1, H, hd) and each K/V page
(1, bs, KV*hd), so every block's last two dims are whole array dims —
the TPU tiling rule — whatever the head counts.  GQA runs one
(group, hd) x (hd, bs) dot per KV head, the group's query heads
sharing that head's page.  Pages past ``ceil(len/bs)`` still iterate
but are fully masked — block-skipping via a per-row page count is the
same documented perf follow-up as flash_attention's masked KV blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.nn.attention import decode_attention

NEG_INF = -1.0e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, logit_cap, bs, pages, kv, hd):
    b = pl.program_id(0)
    pi = pl.program_id(1)
    group = q_ref.shape[1] // kv

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    key_pos = pi * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = key_pos < len_ref[b]
    for g in range(kv):
        rows = slice(g * group, (g + 1) * group)
        q = q_ref[0, rows, :].astype(jnp.float32)              # (group, hd)
        k = k_ref[0, :, g * hd:(g + 1) * hd].astype(jnp.float32)  # (bs, hd)
        v = v_ref[0, :, g * hd:(g + 1) * hd].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_cap > 0.0:
            s = jnp.tanh(s / logit_cap) * logit_cap
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[rows, :]                                # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked page: s == m_new == NEG_INF would give exp(0) = 1
        # — force masked probabilities to exactly zero.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = (l_ref[rows, :] * alpha
                          + jnp.sum(p, axis=-1, keepdims=True))
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[rows, :] = m_new

    @pl.when(pi == pages - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_reference(q, k_pool, v_pool, tables, cache_len, *,
                              logit_cap: float = 0.0,
                              scale: float | None = None):
    """XLA reference: gather the table view, run stock decode attention.

    q: (B, 1, H, hd); pools: (NB, bs, KV, hd); tables: (B, P) int32;
    cache_len: (B,) int32 valid keys per row (current token included).
    """
    b = q.shape[0]
    kv, hd = k_pool.shape[2], k_pool.shape[3]
    kc = jnp.reshape(k_pool[tables], (b, -1, kv, hd))
    vc = jnp.reshape(v_pool[tables], (b, -1, kv, hd))
    return decode_attention(q, kc, vc, cache_len, window=0,
                            logit_cap=logit_cap, scale=scale)


def paged_decode_attention(q, k_pool, v_pool, tables, cache_len, *,
                           logit_cap: float = 0.0,
                           scale: float | None = None,
                           interpret: bool = False):
    """Same contract as ``paged_attention_reference``, via the kernel."""
    b, sq, h, hd = q.shape
    assert sq == 1, "paged decode kernel is single-token"
    nb, bs, kv, _ = k_pool.shape
    assert h % kv == 0
    pages = tables.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    kernel = functools.partial(_kernel, scale=scale, logit_cap=logit_cap,
                               bs=bs, pages=pages, kv=kv, hd=hd)
    page = pl.BlockSpec((1, bs, kv * hd), lambda bi, pi, bt, sl:
                        (bt[bi, pi], 0, 0))
    row = pl.BlockSpec((1, h, hd), lambda bi, pi, bt, sl: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(cache_len, jnp.int32),
      q[:, 0], k_pool.reshape(nb, bs, kv * hd),
      v_pool.reshape(nb, bs, kv * hd))
    return out[:, None]                                   # (B, 1, H, hd)

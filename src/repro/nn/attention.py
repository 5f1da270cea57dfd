"""Attention: GQA/MQA/MHA with chunked (memory-bounded) softmax, sliding
windows, logit soft-capping, decay biases (for mLSTM), and decode paths.

Key implementation choice for 32k+ sequences on 16 GB chips: never
materialize the full (S, S) score matrix.  ``chunked_attention`` loops
over query chunks with ``jax.lax.map``; each chunk attends to either the
full key range (global) or a dynamically-sliced window (local), so peak
memory is O(S * q_chunk) [global] or O(w * q_chunk) [local] per head.
On TPU the Pallas flash kernel (kernels/flash_attention) replaces this
XLA path when `use_pallas` is set; both are validated against
``ref_attention``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .layers import softcap as _softcap

NEG_INF = -2.0e38


def _repeat_kv(k, n_rep: int):
    """(B, S, kv, hd) -> (B, S, kv*n_rep, hd) by head repetition."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)
                            ).reshape(b, s, kv * n_rep, hd)


# ---------------------------------------------------------------------------
# reference (oracle) attention — small shapes only
# ---------------------------------------------------------------------------

def ref_attention(q, k, v, *, causal: bool = True, window: int = 0,
                  logit_cap: float = 0.0, scale: float | None = None,
                  bias=None):
    """q: (B, Sq, H, hd); k,v: (B, Skv, KV, hd).  Returns (B, Sq, H, hd).

    Supports GQA (H multiple of KV), causal masking with `q_offset`
    implied by Skv - Sq (decode-friendly), sliding window, softcap."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)
    scale = scale if scale is not None else hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logit_cap > 0:
        scores = _softcap(scores, logit_cap)
    if bias is not None:
        scores = scores + bias
    skv = k.shape[1]
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# chunked attention (memory-bounded XLA path)
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      logit_cap: float = 0.0, scale: float | None = None,
                      q_chunk: int = 1024, decay=None, unroll: bool = False):
    """Memory-bounded attention; same semantics as ref_attention.

    decay: optional dict(log_fcum=(B,S,H), log_i=(B,S,H)) adding the
    mLSTM decay bias b_ij = log_fcum_i - log_fcum_j + log_i_j and using
    the mLSTM max(|den|, exp(-m)) normalizer instead of softmax's sum.
    """
    b, s_orig, h, hd = q.shape
    kv_heads = k.shape[2]
    n_rep = h // kv_heads
    scale = scale if scale is not None else hd ** -0.5
    q_chunk = min(q_chunk, s_orig)
    # pad queries to a chunk multiple; padded rows are sliced off at the
    # end.  decay has a q side (log_fcum_i) and a k side (log_fcum_j,
    # log_i_j): only the q side follows the query padding.
    s = ((s_orig + q_chunk - 1) // q_chunk) * q_chunk
    decay_q = decay
    if s != s_orig:
        q = jnp.pad(q, ((0, 0), (0, s - s_orig), (0, 0), (0, 0)))
        if decay is not None:
            decay_q = {kk: jnp.pad(vv, ((0, 0), (0, s - s_orig), (0, 0)))
                       for kk, vv in decay.items()}
    s_kv = k.shape[1]
    n_chunks = s // q_chunk

    use_window = window > 0 and window < s
    if use_window:
        # keys for chunk c live in [c*qc - (window-1), c*qc + qc): pad K/V
        # on the left so every chunk slices a fixed-size [window+qc] range,
        # and on the right by the query padding so the dynamic_slice for
        # the last (padded) chunk never clamps and misaligns positions.
        pad = window
        rpad = s - s_orig
        k_pad = jnp.pad(k, ((0, 0), (pad, rpad), (0, 0), (0, 0)))
        v_pad = jnp.pad(v, ((0, 0), (pad, rpad), (0, 0), (0, 0)))

    def one_chunk(c):
        q_c = jax.lax.dynamic_slice_in_dim(q, c * q_chunk, q_chunk, axis=1)
        q_idx = c * q_chunk + jnp.arange(q_chunk)
        if use_window:
            k_c = jax.lax.dynamic_slice_in_dim(k_pad, c * q_chunk,
                                               window + q_chunk, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v_pad, c * q_chunk,
                                               window + q_chunk, axis=1)
            k_idx = c * q_chunk - window + jnp.arange(window + q_chunk)
        else:
            k_c, v_c = k, v
            k_idx = jnp.arange(s_kv)
        k_r = _repeat_kv(k_c, n_rep)
        v_r = _repeat_kv(v_c, n_rep)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_c.astype(jnp.float32),
                            k_r.astype(jnp.float32)) * scale
        if logit_cap > 0:
            scores = _softcap(scores, logit_cap)
        # non-causal unwindowed unpadded chunks mask nothing: skip the
        # where() to save a full read+write of the score tensor (the
        # whisper-encoder memory-term iteration, EXPERIMENTS §Perf 8)
        if not causal and window == 0 and s == s_orig:
            m = jnp.max(scores, axis=-1, keepdims=True)
            e = jnp.exp(scores - m)
            den = jnp.sum(e, axis=-1, keepdims=True)
            out = jnp.einsum("bhqk,bkhd->bqhd", e / den,
                             v_r.astype(jnp.float32))
            return out.astype(q.dtype)
        mask = jnp.ones((q_chunk, k_idx.shape[0]), bool)
        mask &= (k_idx[None, :] >= 0) & (k_idx[None, :] < s_kv)
        if causal:
            mask &= k_idx[None, :] <= q_idx[:, None]
        if window > 0:
            mask &= k_idx[None, :] > q_idx[:, None] - window
        if decay is not None:
            # mLSTM parallel form (xLSTM eq. 24-27): the q.k dot product
            # multiplies OUTSIDE the exponential decay gate.
            #   D~_ij = logsig_fcum_i - logsig_fcum_j + log_i_j  (j <= i)
            #   m_i   = max_j D~_ij;  D'_ij = exp(D~_ij - m_i)
            #   C     = (Q K^T / sqrt(d)) * D'
            #   n_i   = max(|sum_j C_ij|, exp(-m_i));  H = C/n @ V
            lf, li = decay["log_fcum"], decay["log_i"]        # (B,S_kv,H)
            lf_q = jax.lax.dynamic_slice_in_dim(
                decay_q["log_fcum"], c * q_chunk, q_chunk, 1)
            if use_window:
                lf_pad = jnp.pad(lf, ((0, 0), (pad, rpad), (0, 0)))
                li_pad = jnp.pad(li, ((0, 0), (pad, rpad), (0, 0)))
                lf_k = jax.lax.dynamic_slice_in_dim(lf_pad, c * q_chunk,
                                                    window + q_chunk, 1)
                li_k = jax.lax.dynamic_slice_in_dim(li_pad, c * q_chunk,
                                                    window + q_chunk, 1)
            else:
                lf_k, li_k = lf, li
            dmat = (lf_q[:, :, None, :].transpose(0, 3, 1, 2)
                    - lf_k[:, None, :, :].transpose(0, 3, 1, 2)
                    + li_k[:, None, :, :].transpose(0, 3, 1, 2))
            dmat = jnp.where(mask[None, None], dmat, NEG_INF)
            m = jnp.max(dmat, axis=-1, keepdims=True)
            m = jnp.maximum(m, -30.0)                        # numeric floor
            cmat = scores * jnp.exp(dmat - m)
            den = jnp.maximum(jnp.abs(jnp.sum(cmat, axis=-1, keepdims=True)),
                              jnp.exp(-m))
            out = jnp.einsum("bhqk,bkhd->bqhd", cmat / den,
                             v_r.astype(jnp.float32))
            return out.astype(q.dtype)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        m = jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores - m)
        den = jnp.sum(e, axis=-1, keepdims=True)
        out = jnp.einsum("bhqk,bkhd->bqhd", e / den, v_r.astype(jnp.float32))
        return out.astype(q.dtype)

    if unroll:
        # python-unrolled chunk loop: used by the dry-run cost probes so
        # XLA's HloCostAnalysis (which counts while bodies once) sees
        # every chunk; numerically identical to the lax.map path.
        out = jnp.stack([one_chunk(jnp.asarray(c)) for c in range(n_chunks)])
    else:
        out = jax.lax.map(one_chunk, jnp.arange(n_chunks))  # (C,B,qc,H,hd)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)
    return out[:, :s_orig]


# ---------------------------------------------------------------------------
# decode attention (single query position against a cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     logit_cap: float = 0.0, scale: float | None = None):
    """q: (B, 1, H, hd); caches: (B, S_max, KV, hd); cache_len: scalar or
    (B,) — number of valid cache positions (new token already written).
    Window semantics match chunked_attention (last `window` positions)."""
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    s_max = k_cache.shape[1]
    k_r = _repeat_kv(k_cache, h // kv)
    v_r = _repeat_kv(v_cache, h // kv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_r.astype(jnp.float32)) * scale    # (B,H,1,S)
    if logit_cap > 0:
        scores = _softcap(scores, logit_cap)
    pos = jnp.arange(s_max)[None, :]
    limit = jnp.asarray(cache_len).reshape(-1, 1)
    valid = pos < limit
    if window > 0:
        valid &= pos >= limit - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    e = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    w = e / _tree_sum(e)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v_r.astype(jnp.float32))
    return out.astype(q.dtype)


def _tree_sum(x):
    """Sum over the last axis, keepdims, in an order the code fixes:
    halves are added elementwise until one column is left.

    A reduction's order is the compiler's choice, and on a TPU it follows
    the whole program's shapes: a softmax denominator XLA computes as a
    full-row reduce-window on one chip becomes a plain reduce on each
    shard of a (2, 2) mesh, and the two round differently.  Elementwise
    adds are never reassociated, so this sum gives the same bits for a
    row whatever batch or heads share its program (DESIGN.md §8)."""
    while x.shape[-1] > 1:
        if x.shape[-1] % 2:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 1)])
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x

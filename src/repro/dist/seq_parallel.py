"""Sequence-parallel decode attention: the KV cache is sharded along its
sequence dimension over a mesh axis; each shard computes a partial
flash-style softmax over its local positions and the shards combine with
one pmax + two psums of (B, H, hd)-sized tensors — never gathering the
cache (the point of SP decode for 500k-token contexts).

Numerically identical to ``nn.attention.decode_attention`` (same mask,
scale, GQA head repeat); verified in test_multidevice.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def sp_decode_attention(q, k_cache, v_cache, cache_len, mesh,
                        seq_axis: str = "data", *,
                        logit_cap: float = 0.0,
                        scale: float | None = None):
    """q: (B, 1, H, hd); caches: (B, S, KV, hd) sharded on S over
    `seq_axis`; cache_len: number of valid cache positions."""
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    scale = scale if scale is not None else hd ** -0.5
    n_shards = int(mesh.shape[seq_axis])
    s_local = k_cache.shape[1] // n_shards
    cache_len = jnp.asarray(cache_len, jnp.int32)

    def body(q_l, k_l, v_l):
        # local shard: positions [offset, offset + s_local)
        offset = jax.lax.axis_index(seq_axis) * s_local
        k_r = jnp.repeat(k_l.astype(jnp.float32), rep, axis=2)
        v_r = jnp.repeat(v_l.astype(jnp.float32), rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_l.astype(jnp.float32),
                            k_r) * scale                       # (B,H,1,Sl)
        if logit_cap > 0:
            scores = jnp.tanh(scores / logit_cap) * logit_cap
        pos = offset + jnp.arange(s_local)
        valid = pos[None, :] < cache_len.reshape(-1, 1)        # (B,Sl)
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        m_loc = jnp.max(scores, axis=-1)                       # (B,H,1)
        m_glob = jax.lax.pmax(m_loc, seq_axis)
        p = jnp.exp(scores - m_glob[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)                            # (B,H,1)
        o_loc = jnp.einsum("bhqk,bkhd->bqhd", p, v_r)          # (B,1,H,hd)
        l_glob = jax.lax.psum(l_loc, seq_axis)
        o_glob = jax.lax.psum(o_loc, seq_axis)
        denom = jnp.maximum(l_glob, 1e-30)                     # (B,H,1)
        return (o_glob / denom.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    spec_kv = P(None, seq_axis, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), spec_kv, spec_kv),
        out_specs=P(),
        check_vma=False,
    )(q, k_cache, v_cache)

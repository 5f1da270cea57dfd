"""Plain reference of a decoder-only transformer LM whose GEMMs run on
the paper's error-configurable int8 MAC (dense SwiGLU or top-k routed
experts), and the seeded weights both it and the program are served.

Everything here is straightforward ``jax.numpy``: float32 activations,
norms, rotary embedding, softmax and LM head at ``HIGHEST`` matmul
precision, and every layer GEMM as the MAC semantics state it:

* the activation row is quantized symmetrically to ``qmax`` (127 for
  int8) with its own abs-max scale, the weight per output channel
  (its stored int8 values and scale);
* under error config c both operands lose their low magnitude bits by
  operand truncation (depth, gate and rounding from ``OPERAND_PARAMS``),
  and the product accumulates exactly in int32;
* the int32 sum is rescaled by ``x_scale * w_scale``.

Each row (position) carries its own config, the one the served step
that computed that position ran at.  Routed experts follow the served
deployment's dispatch: top-k of the softmax router, no renormalisation,
and during prefill a capacity of ``ceil(chunk * k / E * capacity)``
entries per expert and prompt chunk, first come first kept; decode
rows are dropless.

With ``qmax=7`` the same code is the int4 control: the nearest lower
precision than the int8 the configurations state.  The work it counts
is ``bench/work.py`` ``Shapes``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.work import Shapes

WORK = Shapes
HIGHEST = jax.lax.Precision.HIGHEST
QMAX_INT8 = 127
QMAX_INT4 = 7

# (mode, product truncation depth t, operand gate) of configs 1..31 of
# the paper's error-configurable multiplier (the functional family the
# program calibrates to the paper's Table I); mode 0 floors, the others
# round to nearest.  Depth t splits over the operands: t // 2 on the
# activation, t - t // 2 on the weight.
_CONFIGS = (
    (1, 1, 48), (2, 2, 56), (0, 1, 48), (1, 1, 0), (0, 1, 0), (0, 2, 0),
    (2, 3, 0), (3, 9, 48), (0, 9, 48), (2, 10, 48), (3, 7, 32),
    (1, 10, 48), (0, 8, 40), (0, 7, 32), (0, 6, 24), (2, 9, 40),
    (3, 9, 40), (1, 9, 40), (2, 8, 32), (0, 9, 40), (3, 8, 32),
    (1, 8, 32), (2, 10, 40), (0, 8, 32), (1, 10, 40), (2, 9, 32),
    (3, 9, 32), (1, 9, 32), (0, 9, 32), (2, 10, 32), (1, 10, 32))
OPERAND_PARAMS = ((0, 0, 0, 0),) + tuple(
    (t // 2, t - t // 2, gate, int(mode != 0)) for mode, t, gate in _CONFIGS)


def truncate(v, depth: int, gate: int, rtn: int):
    """Drop `depth` low magnitude bits of the int32 values `v` whose
    magnitude is at least `gate` (rounding to nearest when `rtn`,
    clamped to 127), keeping the sign."""
    if depth == 0:
        return v
    mag, sign = jnp.abs(v), jnp.sign(v)
    low = (1 << depth) - 1
    if rtn:
        t = jnp.minimum((mag + (1 << (depth - 1))) & ~low, QMAX_INT8)
    else:
        t = mag & ~low
    return sign * jnp.where(mag >= gate, t, mag)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

_SPREAD = 3.5        # an int8 weight column spans +-3.5 standard deviations


def _int8_matrix(key, shape, std):
    """(values int8, per-output-channel scale f32) of a Gaussian matrix
    of standard deviation `std`; the last axis is the output channel."""
    kv, ks = jax.random.split(key)
    q = QMAX_INT8 / _SPREAD
    z = jax.random.normal(kv, shape, jnp.float32)
    values = jnp.clip(jnp.round(z * q), -QMAX_INT8, QMAX_INT8
                      ).astype(jnp.int8)
    scale_shape = shape[:-2] + shape[-1:]
    scale = (std / q) * jnp.exp(0.1 * jax.random.normal(ks, scale_shape))
    return values, scale.astype(jnp.float32)


def make_weights(model: dict, key):
    """The served weights as a plain dict, made on the device from `key`:
    int8 GEMM weights with per-output-channel scales, float32 embedding,
    norms, biases, router and LM head.  Per-layer arrays are stacked on a
    leading layer axis."""
    d, h, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd, f, n_layers = model["head_dim"], model["intermediate_size"], \
        model["num_hidden_layers"]
    n_exp = model.get("num_experts", 0)
    vocab = model["vocab_size"]

    def layer(k):
        ks = jax.random.split(k, 12)
        out = {"norm1": 0.1 * jax.random.normal(ks[0], (d,)),
               "norm2": 0.1 * jax.random.normal(ks[1], (d,))}
        for name, kk, n in (("wq", ks[2], h), ("wk", ks[3], kv),
                            ("wv", ks[4], kv)):
            out[name], out[name + "_s"] = _int8_matrix(
                kk, (d, n * hd), 1 / math.sqrt(d))
        out["wo"], out["wo_s"] = _int8_matrix(
            ks[5], (h * hd, d), 1 / math.sqrt(h * hd * n_layers))
        if model.get("attention_bias"):
            out["bq"] = 0.1 * jax.random.normal(ks[6], (h, hd))
            out["bk"] = 0.1 * jax.random.normal(ks[7], (kv, hd))
            out["bv"] = 0.1 * jax.random.normal(ks[8], (kv, hd))
        lead = (n_exp,) if n_exp else ()
        if n_exp:
            out["router"] = jax.random.normal(ks[9], (d, n_exp)) / math.sqrt(d)
        out["w_gate"], out["w_gate_s"] = _int8_matrix(
            ks[10], lead + (d, f), 1 / math.sqrt(d))
        ku, kd = jax.random.split(ks[11])
        out["w_up"], out["w_up_s"] = _int8_matrix(
            ku, lead + (d, f), 1 / math.sqrt(d))
        out["w_down"], out["w_down_s"] = _int8_matrix(
            kd, lead + (f, d), 1 / math.sqrt(f))
        return out

    ke, kl, kn, kh = jax.random.split(key, 4)
    w = {"embed": 0.02 * jax.random.normal(ke, (vocab, d)),
         "final_norm": 0.1 * jax.random.normal(kn, (d,)),
         "layers": jax.lax.map(layer, jax.random.split(kl, n_layers))}
    if not model.get("tie_word_embeddings"):
        w["lm_head"] = jax.random.normal(kh, (d, vocab)) / math.sqrt(d)
    return w


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)[None, :]


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate the two halves by position."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs[None, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _quant_rows(x, qmax, mask=None):
    """Per-row symmetric quantization of x (S, ...): int32 values and
    the (S,) scale; `mask` zeroes entries left out of the abs-max."""
    a = jnp.abs(x) if mask is None else jnp.abs(jnp.where(mask, x, 0.0))
    amax = jnp.max(a.reshape(a.shape[0], -1), axis=1)
    s = jnp.maximum(amax, 1e-12) / qmax
    sb = s.reshape((-1,) + (1,) * (x.ndim - 1))
    q = jnp.clip(jnp.round(x / sb), -qmax, qmax).astype(jnp.int32)
    return q, s


def _weight_at(values, scale, qmax):
    """(int32 values, scale) of an int8 weight at `qmax` levels: as
    stored for int8, requantized per output channel below it."""
    v = values.astype(jnp.int32)
    if qmax == QMAX_INT8:
        return v, scale
    amax = jnp.maximum(jnp.max(jnp.abs(v), axis=-2, keepdims=True), 1)
    q = jnp.clip(jnp.round(v * (qmax / amax)), -qmax, qmax
                 ).astype(jnp.int32)
    return q, scale * (amax[..., 0, :] / qmax)


def _mac(xq, wq, cfg_rows, cfgs, spec):
    """int32 accumulation of einsum(spec, xq, wq) with each row's
    operands truncated at its own config: cfg_rows (S,) indexes `cfgs`."""
    acc = None
    for i, c in enumerate(cfgs):
        da, db, gate, rtn = OPERAND_PARAMS[c]
        a = truncate(xq, da, gate, rtn).astype(jnp.int8)
        b = truncate(wq, db, gate, rtn).astype(jnp.int8)
        y = jnp.einsum(spec, a, b, preferred_element_type=jnp.int32)
        sel = (cfg_rows == i).reshape((-1,) + (1,) * (y.ndim - 1))
        acc = y if acc is None else jnp.where(sel, y, acc)
    return acc


def _gemm(x, values, scale, cfg_rows, cfgs, qmax):
    """x (S, K) float32 @ an int8 weight (K, N) on the MAC."""
    xq, xs = _quant_rows(x, qmax)
    wq, ws = _weight_at(values, scale, qmax)
    acc = _mac(xq, wq, cfg_rows, cfgs, "sk,kn->sn")
    return acc.astype(jnp.float32) * (xs[:, None] * ws[None, :])


def _moe(x, lw, model, cfg_rows, cfgs, qmax, n_prompt, chunk, capacity):
    """Routed experts over x (S, d)."""
    n_exp, k = model["num_experts"], model["num_experts_per_tok"]
    s_len = x.shape[0]
    probs = jax.nn.softmax(jnp.dot(x, lw["router"], precision=HIGHEST),
                           axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                      # (S, k)
    if model.get("norm_topk_prob"):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # prefill capacity per prompt chunk, in (row, k) order; decode dropless
    onehot = jax.nn.one_hot(top_e.reshape(-1), n_exp, dtype=jnp.int32)
    seen = jnp.cumsum(onehot, axis=0)                           # inclusive
    row = jnp.arange(s_len * k) // k
    start = (row // chunk) * chunk * k
    before = jnp.where(start[:, None] > 0,
                       seen[jnp.maximum(start - 1, 0)], 0)
    rank = jnp.sum((seen - before - 1) * onehot, axis=1)
    cap = min(math.ceil(chunk * k / n_exp * capacity), chunk * k)
    keep = ((rank < cap) | (row >= n_prompt)).reshape(s_len, k)
    w_route = jnp.zeros((s_len, n_exp), jnp.float32).at[
        jnp.arange(s_len)[:, None], top_e].add(jnp.where(keep, top_p, 0.0))
    chosen = w_route > 0                                        # (S, E)

    xq, xs = _quant_rows(x, qmax)
    gv, gs = _weight_at(lw["w_gate"], lw["w_gate_s"], qmax)
    uv, us = _weight_at(lw["w_up"], lw["w_up_s"], qmax)
    dv, ds = _weight_at(lw["w_down"], lw["w_down_s"], qmax)
    gate = _mac(xq, gv, cfg_rows, cfgs, "sd,edf->sef").astype(jnp.float32) \
        * (xs[:, None, None] * gs[None])
    up = _mac(xq, uv, cfg_rows, cfgs, "sd,edf->sef").astype(jnp.float32) \
        * (xs[:, None, None] * us[None])
    mid = jax.nn.silu(gate) * up                                # (S, E, f)
    hq, hs = _quant_rows(mid, qmax, mask=chosen[:, :, None])
    out = _mac(hq, dv, cfg_rows, cfgs, "sef,efd->sed").astype(jnp.float32) \
        * (hs[:, None, None] * ds[None])                        # (S, E, d)
    return jnp.einsum("sed,se->sd", out, w_route, precision=HIGHEST)


def forward_logits(w, model: dict, tokens, cfg_rows, n_real, n_prompt, sel,
                   *, cfgs: tuple, qmax: int = QMAX_INT8, chunk: int = 1,
                   capacity: float = 1.0, norm_eps: float = 1e-6):
    """Logits (len(sel), vocab) at rows `sel` of the sequence `tokens`.

    tokens (S,) int32 of which the first `n_real` are real (the rest pad
    the shape); cfg_rows (S,) int32 indexes `cfgs`, the config each row
    ran at; `n_prompt` rows are prompt (prefill capacity applies);
    `chunk` and `capacity` are the deployment's prefill chunk and expert
    capacity factor."""
    d, h, kv = model["hidden_size"], model["num_attention_heads"], \
        model["num_key_value_heads"]
    hd = model["head_dim"]
    s_len = tokens.shape[0]
    pos = jnp.arange(s_len)
    x = w["embed"][tokens].astype(jnp.float32)
    q_idx, k_idx = pos[:, None], pos[None, :]
    allowed = (k_idx <= q_idx) & (k_idx < n_real)
    rep = h // kv

    def layer(x, lw):
        hn = _rmsnorm(x, lw["norm1"], norm_eps)
        q = _gemm(hn, lw["wq"], lw["wq_s"], cfg_rows, cfgs, qmax)
        k = _gemm(hn, lw["wk"], lw["wk_s"], cfg_rows, cfgs, qmax)
        v = _gemm(hn, lw["wv"], lw["wv_s"], cfg_rows, cfgs, qmax)
        q, k, v = (a.reshape(s_len, n, hd)
                   for a, n in ((q, h), (k, kv), (v, kv)))
        if "bq" in lw:
            q, k, v = q + lw["bq"][None], k + lw["bk"][None], \
                v + lw["bv"][None]
        q = _rope(q, pos, model["rope_theta"])
        k = _rope(k, pos, model["rope_theta"])
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
        sc = jnp.where(allowed[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + _gemm(o.reshape(s_len, h * hd), lw["wo"], lw["wo_s"],
                      cfg_rows, cfgs, qmax)
        hn = _rmsnorm(x, lw["norm2"], norm_eps)
        if model.get("num_experts"):
            y = _moe(hn, lw, model, cfg_rows, cfgs, qmax, n_prompt, chunk,
                     capacity)
        else:
            g = _gemm(hn, lw["w_gate"], lw["w_gate_s"], cfg_rows, cfgs, qmax)
            u = _gemm(hn, lw["w_up"], lw["w_up_s"], cfg_rows, cfgs, qmax)
            y = _gemm(jax.nn.silu(g) * u, lw["w_down"], lw["w_down_s"],
                      cfg_rows, cfgs, qmax)
        return x + y, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    hsel = _rmsnorm(x[sel], w["final_norm"], norm_eps)
    head = w["embed"].T if model.get("tie_word_embeddings") else w["lm_head"]
    return jnp.dot(hsel, head, precision=HIGHEST)

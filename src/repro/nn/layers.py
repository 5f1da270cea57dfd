"""Building-block layers: norms, dense (exact / quantized-approximate),
embeddings, RoPE.  Pure functions over param dicts.

Every dense layer can run in three modes (per-layer, runtime-selectable):
  * float (training / exact serving)
  * quantized exact (config 0): dynamic int8 activations x int8 weights
  * quantized approximate (configs 1..31): the paper's error knob via
    ``approx_dense`` (operand-truncation TPU path)

The error config for a layer comes from the ``approx_cfg`` argument
threading through the model apply functions; 0 everywhere by default.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.approx_matmul import approx_dense
from repro.core.quantization import (QTensor, expand_left, fake_quant,
                                     quantize)

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(rng, d_in: int, d_out: int, dtype=jnp.float32,
               scale: float | None = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out)) * scale).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(rng, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# dense with the error-config knob
# ---------------------------------------------------------------------------

@jax.named_scope("gemm")
def dense(x, w, *, approx_cfg: int = 0, quantized: bool = False,
          compute_dtype=jnp.bfloat16, backend: str = "xla",
          interpret: bool = False,
          block_shapes: tuple[int, int, int] = (128, 128, 256)):
    """y = x @ w under the selected arithmetic mode.

    w may be a float array or a QTensor (pre-quantized weights — see
    transformer.quantize_lm_params; quantizing once at load time instead
    of inside every traced call removes a per-decode-step requantize).
    When `quantized` or approx_cfg>0, runs the integer pipeline: dynamic
    per-tensor int8 activations x int8 weights, operand-truncation
    approximation, f32 rescale (DESIGN.md §2).

    `approx_cfg` may be a TRACED int32 scalar (the runtime power knob):
    the integer pipeline then always runs, with the error config gathered
    per call — traced config 0 is the exact int8 MAC (the paper's exact
    mode), bit-identical to the static quantized path.  On the "pallas"
    backend it may also be a (g,) per-neuron-group config VECTOR: group
    j covers output columns [j*N/g, (j+1)*N/g) at the kernel's
    bn-column block resolution; blocks straddling a group boundary (or
    GEMMs narrower than g blocks) run the lowest-measured-MRED config
    among their groups — never higher error than any covered neuron
    asked for (DESIGN.md §3).  An (E, g) per-EXPERT matrix (an engine
    config with an expert axis reaching a GEMM that has no expert
    dimension) collapses the expert axis per group by the same
    lowest-measured-MRED rule (DESIGN.md §4).

    backend: "xla" (operand-truncation ops compiled by XLA) or "pallas"
    (the fused approx-MAC kernel: quantize + truncate + int8 MAC +
    rescale in one pallas_call).  Both are bit-identical per config;
    `interpret` runs the kernel in interpret mode (CPU tests);
    `block_shapes` is the kernel's (bm, bn, bk) tiling — results are
    tiling-invariant, so feed it an autotune_block_shapes winner."""
    vector_cfg = isinstance(approx_cfg, jax.Array) and approx_cfg.ndim >= 1
    if isinstance(approx_cfg, jax.Array) or approx_cfg > 0 or quantized:
        w_qt = w if isinstance(w, QTensor) else quantize(w, axis=1)
        if backend == "pallas":
            from repro.kernels.approx_mac.ops import (approx_dense_pallas,
                                                      collapse_expert_cfg)
            if isinstance(approx_cfg, jax.Array) and approx_cfg.ndim == 2:
                approx_cfg = collapse_expert_cfg(approx_cfg)
            bm, bn, bk = block_shapes
            y = approx_dense_pallas(x.astype(jnp.float32), w_qt,
                                    config=approx_cfg, interpret=interpret,
                                    bm=bm, bn=bn, bk=bk,
                                    compute_dtype=jnp.float32)
            return y.astype(compute_dtype)
        assert not vector_cfg, \
            "per-block config vectors require backend='pallas'"
        y = approx_dense(x.astype(jnp.float32), w_qt, approx_cfg)
        return y.astype(compute_dtype)
    if isinstance(w, QTensor):
        w = w.dequantize()
    return jnp.dot(x, w.astype(x.dtype))


def qat_dense(x, w, *, compute_dtype=jnp.bfloat16):
    """Quantization-aware training path (straight-through fake quant)."""
    return jnp.dot(fake_quant(x.astype(jnp.float32)),
                   fake_quant(w.astype(jnp.float32), axis=1)).astype(compute_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6, offset: float = 1.0):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    s = offset + scale.astype(jnp.float32)
    return (y * expand_left(s, y.ndim)).astype(dt)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * expand_left(scale.astype(jnp.float32), y.ndim)
            + expand_left(bias.astype(jnp.float32), y.ndim)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    pos = positions[..., :, None, None].astype(jnp.float32)
    ang = pos * expand_left(freqs, pos.ndim)            # (...,S,1,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / misc
# ---------------------------------------------------------------------------

def softcap(x, cap: float):
    """tanh logit soft-capping (Gemma-2)."""
    return jnp.tanh(x / cap) * cap


ACT = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}

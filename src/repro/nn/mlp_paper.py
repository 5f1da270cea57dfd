"""The paper's MLP: 62 -> 30 (hidden, ReLU) -> 10, signed-magnitude 8-bit.

Float training graph + quantized/approximate inference graph.  The
quantized graph follows the paper's datapath semantics exactly:

  per neuron:  acc21 = sum_k approx_mult(x_k, w_k)      (21-bit signed acc)
               acc   = acc21 + bias_aligned
               relu  = max(acc, 0)
               out8  = saturate(acc >> shift)            (clip to [0,127])

Bias alignment: the paper stores 8-bit biases; inside the MAC result
domain the bias must be scaled by (s_x * s_w / s_b)^-1 ... we keep the
standard integer-pipeline choice: bias is quantized directly in the
accumulator scale (s_x*s_w), i.e. b_int = round(b / (s_x*s_w)), which a
real controller would precompute.  `shift` per layer realigns the 21-bit
accumulator to the next layer's 8-bit input domain and is chosen at
quantization time from calibration data (the paper's "saturation
section"; exact shift values are not given in the paper).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.approx_matmul import approx_matmul_lut, approx_matmul_operand
from repro.core.quantization import QMAX, expand_left, quantize_np

N_INPUT, N_HIDDEN, N_OUTPUT = 62, 30, 10


# ---------------------------------------------------------------------------
# float model (training)
# ---------------------------------------------------------------------------

def init_params(rng, n_in: int = N_INPUT, n_hidden: int = N_HIDDEN,
                n_out: int = N_OUTPUT):
    k1, k2 = jax.random.split(rng)
    s1 = np.sqrt(2.0 / n_in)
    s2 = np.sqrt(2.0 / n_hidden)
    return {
        "hidden": {"w": jax.random.normal(k1, (n_in, n_hidden)) * s1,
                   "b": jnp.zeros((n_hidden,))},
        "out": {"w": jax.random.normal(k2, (n_hidden, n_out)) * s2,
                "b": jnp.zeros((n_out,))},
    }


def apply_float(params, x):
    h = jax.nn.relu(x @ params["hidden"]["w"]
                    + expand_left(params["hidden"]["b"], x.ndim))
    return h @ params["out"]["w"] + expand_left(params["out"]["b"], h.ndim)


# ---------------------------------------------------------------------------
# quantized model (paper datapath semantics)
# ---------------------------------------------------------------------------

@dataclass
class QuantizedMLP:
    """Frozen integer parameters + scales, built from trained float params."""
    w1: np.ndarray          # (62, 30) int8
    b1: np.ndarray          # (30,)    int32, accumulator domain
    w2: np.ndarray          # (30, 10) int8
    b2: np.ndarray          # (10,)    int32
    x_scale: float          # input quant scale (images pre-scaled to [0,1])
    s1: float               # w1 scale
    shift1: int             # hidden-layer saturation shift
    h_scale: float          # effective scale of the 8-bit hidden activations
    s2: float               # w2 scale
    meta: dict = field(default_factory=dict)

    @staticmethod
    def from_float(params, calib_x: np.ndarray) -> "QuantizedMLP":
        """Quantize a trained float model; pick saturation shifts from
        calibration activations so the int pipeline tracks the float one."""
        w1f = np.asarray(params["hidden"]["w"], np.float32)
        b1f = np.asarray(params["hidden"]["b"], np.float32)
        w2f = np.asarray(params["out"]["w"], np.float32)
        b2f = np.asarray(params["out"]["b"], np.float32)

        x_scale = float(np.abs(calib_x).max() / QMAX) or 1.0 / QMAX
        w1, s1 = quantize_np(w1f)
        s1 = float(s1)
        acc_scale1 = x_scale * s1
        b1 = np.round(b1f / acc_scale1).astype(np.int32)

        # float hidden activations on calibration data -> choose shift so
        # the 8-bit saturated output covers the observed range.
        xq = np.clip(np.round(calib_x / x_scale), -QMAX, QMAX).astype(np.int32)
        acc = xq @ w1.astype(np.int32) + b1
        acc = np.maximum(acc, 0)
        amax = max(float(acc.max()), 1.0)
        shift1 = max(int(np.ceil(np.log2(amax / QMAX))), 0)
        h_scale = acc_scale1 * (1 << shift1)

        w2, s2 = quantize_np(w2f)
        s2 = float(s2)
        b2 = np.round(b2f / (h_scale * s2)).astype(np.int32)
        return QuantizedMLP(w1=w1, b1=b1, w2=w2, b2=b2, x_scale=x_scale,
                            s1=s1, shift1=shift1, h_scale=h_scale, s2=s2)

    # -- inference ---------------------------------------------------------
    def quantize_input(self, x: np.ndarray) -> np.ndarray:
        return np.clip(np.round(np.asarray(x) / self.x_scale),
                       -QMAX, QMAX).astype(np.int8)

    @staticmethod
    def _layer_configs(config):
        """Normalize `config` to per-layer (hidden, out) configs.

        Accepts a single int (both layers), a length-2 sequence/array of
        per-layer configs, or traced int32 scalars — the runtime knob
        extends down to the paper's own 62-30-10 network."""
        if isinstance(config, (tuple, list)):
            c1, c2 = config
            return c1, c2
        if isinstance(config, (np.ndarray, jax.Array)) \
                and getattr(config, "ndim", 0) == 1:
            return config[0], config[1]
        return config, config

    def apply(self, x_q, config=0, method: str = "lut",
              interpret: bool = False):
        """Integer forward pass under error config `config` (jax arrays).

        x_q: (B, 62) int8.  Returns (B, 10) int32 logits (accumulator
        domain of the output layer — argmax semantics identical to the
        hardware's maximum-value circuit).  method: "lut" (bit-exact
        ASIC oracle), "operand" (TPU-native XLA adaptation), or
        "pallas" (the approx-MAC kernel — same operand semantics, run
        through the fused serving kernel; `interpret=True` runs it in
        Pallas interpret mode, as on CPU)."""
        if method == "pallas":
            from repro.kernels.approx_mac.ops import approx_mac
            mm = lambda a, b, c: approx_mac(a, b, c, interpret=interpret)
        else:
            mm = (approx_matmul_lut if method == "lut"
                  else approx_matmul_operand)
        c1, c2 = self._layer_configs(config)
        x_q = jnp.asarray(x_q)
        acc1 = mm(x_q, jnp.asarray(self.w1), c1) \
            + expand_left(jnp.asarray(self.b1), x_q.ndim)
        acc1 = jnp.maximum(acc1, 0)                       # ReLU (21-bit domain)
        h = jnp.clip(acc1 >> self.shift1, 0, QMAX).astype(jnp.int8)  # saturate
        acc2 = mm(h, jnp.asarray(self.w2), c2) \
            + expand_left(jnp.asarray(self.b2), h.ndim)
        return acc2

    def predict(self, x: np.ndarray, config=0, method: str = "lut"):
        logits = self.apply(self.quantize_input(x), config, method)
        return np.asarray(jnp.argmax(logits, axis=-1))

    def accuracy(self, x: np.ndarray, y: np.ndarray, config=0,
                 method: str = "lut") -> float:
        return float((self.predict(x, config, method) == np.asarray(y)).mean())

    # accumulator-width check (paper: 21-bit MAC output register)
    def max_abs_accumulator(self, x: np.ndarray, config: int = 0) -> int:
        x_q = self.quantize_input(x)
        acc1 = approx_matmul_lut(jnp.asarray(x_q), jnp.asarray(self.w1), config)
        return int(jnp.max(jnp.abs(acc1)))

"""Composable LM assembly covering all 10 assigned architectures.

One ``ModelConfig`` describes dense / MoE / VLM / enc-dec / SSM / hybrid
variants through a layer `pattern` (cycled over the depth):

  "global"     full causal attention          (all dense/MoE archs)
  "local"      sliding-window causal attention (gemma2, danube3, griffin)
  "recurrent"  Griffin RG-LRU block            (recurrentgemma)
  "mlstm"      xLSTM matrix-memory block
  "slstm"      xLSTM scalar-memory block

Parameters are plain nested dicts.  Layers are grouped by one pattern
period and scanned with ``jax.lax.scan`` (config.scan_layers) so the HLO
stays small at 132 B scale; every init function also returns a parallel
*logical sharding spec* tree (tuples of logical axis names per dim) that
``dist/sharding.py`` maps onto the mesh (TP on "model", FSDP on "data").

Three lowerable entry points per architecture:
  * ``forward``        — full-sequence activations (training / prefill)
  * ``prefill``        — forward + KV/state cache construction
  * ``decode_step``    — one token against the cache

The paper's error-config knob threads through every GEMM via
``approx_cfg`` (0 = exact float path).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantization import QTensor, expand_left, quantize
from .attention import chunked_attention, decode_attention
from .layers import ACT, dense, dense_init, embed_init, layernorm, rmsnorm, softcap
from .moe import LayerBank, moe_ffn, repeated
from .recurrent import (mlstm_block_init, mlstm_parallel, mlstm_step,
                        recurrent_block, recurrent_block_init,
                        slstm_block_init, slstm_scan, slstm_step)

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"        # dense | moe | vlm | audio | ssm | hybrid
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab_size: int = 1024
    pattern: tuple[str, ...] = ("global",)
    window: int = 0                      # sliding window for "local"
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    mlp: str = "swiglu"                  # swiglu | geglu | gelu | none
    act: str = "silu"
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    query_scale: float | None = None     # None -> head_dim**-0.5
    norm: str = "rms"                    # rms | ln
    post_norm: bool = False              # gemma2 extra post-norms
    embed_scale: bool = False            # gemma multiplies embed by sqrt(d)
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    renormalize: bool = True
    moe_groups: int = 1                  # dispatch groups (align with DP shards)
    moe_seq_chunks: int = 1              # sequential MoE sub-chunks (prefill)
    moe_ep: bool = False                 # expert-parallel (E over "model")
                                         # instead of TP on d_ff
    moe_grouped: bool = True             # pallas backend: ONE grouped
                                         # kernel over all experts (False =
                                         # per-expert lax.map A/B path)
    # enc-dec (whisper)
    encoder_decoder: bool = False
    n_enc_layers: int = 0
    enc_frame_dim: int = 0               # stub frontend embedding dim == d_model
    max_positions: int = 8192            # learned pos-emb table (ln norm archs)
    # VLM
    vision_prefix_len: int = 0
    # recurrent
    lru_width: int = 0
    mlstm_proj_factor: float = 2.0
    # approx-MAC execution backend for every dense GEMM (DESIGN.md §3):
    # "xla" = operand-truncation ops compiled by XLA; "pallas" = the
    # fused approx-MAC kernel (quantize + truncate + int8 MAC + rescale
    # in one pallas_call, per-N-block config vectors supported).
    # mac_interpret runs the kernel in interpret mode (CPU tests/CI).
    # mac_blocks = the kernel's (bm, bn, bk) tile shape — feed it the
    # winner of kernels.approx_mac.ops.autotune_block_shapes on TPU.
    mac_backend: str = "xla"
    mac_interpret: bool = False
    mac_blocks: tuple[int, int, int] = (128, 128, 256)
    # runtime/execution
    scan_layers: bool = True
    remat: bool = True
    remat_policy: str = "nothing"        # nothing | dots
    q_chunk: int = 1024
    compute_dtype: Any = jnp.bfloat16
    kv_quant: bool = False               # int8 KV cache
    kv_onehot_write: bool = False        # shard-local cache write (decode
                                         # with a sequence-sharded cache)
    loss_chunks: int = 8                 # chunked vocab CE
    unroll_chunks: bool = False          # dry-run cost-probe mode
    def n_groups(self) -> int:
        return self.n_layers // len(self.pattern)

    def remainder_layers(self) -> int:
        return self.n_layers % len(self.pattern)

    def layer_kinds(self) -> list[str]:
        return [self.pattern[i % len(self.pattern)]
                for i in range(self.n_layers)]

    def smoke(self, **over) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        def down(v, lo, q=1):
            return max(lo, int(v) // q)
        base = dict(
            n_layers=max(2 * len(self.pattern), 2),
            d_model=64, n_heads=2,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads > 1 else 1,
            head_dim=32, d_ff=128 if self.d_ff else 0, vocab_size=128,
            window=min(self.window, 16) if self.window else 0,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            n_enc_layers=2 if self.encoder_decoder else 0,
            vision_prefix_len=4 if self.vision_prefix_len else 0,
            lru_width=64 if self.lru_width else 0,
            moe_groups=1, scan_layers=False, remat=False,
            q_chunk=8, loss_chunks=2, max_positions=128,
            compute_dtype=jnp.float32,
        )
        base.update(over)
        return dataclasses.replace(self, **base)


# ---------------------------------------------------------------------------
# per-block init (+ logical sharding specs)
# ---------------------------------------------------------------------------
# logical axes: "fsdp" (zero-3 over data), "tp" (tensor-parallel over
# model), "tp?" (tp if divisible at mapping time else replicated),
# "vocab" (== tp), None (replicated)

def _norm_init(cfg):
    if cfg.norm == "rms":
        return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}, \
               {"scale": (None,)}
    return ({"scale": jnp.ones((cfg.d_model,), jnp.float32),
             "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
            {"scale": (None,), "bias": (None,)})


def _apply_norm(p, x, cfg):
    if cfg.norm == "rms":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def _attn_init(rng, cfg, cross: bool = False):
    ks = jax.random.split(rng, 5)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    std = 1.0 / np.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, h, hd)) * std).astype(jnp.float32),
        "wk": (jax.random.normal(ks[1], (d, kv, hd)) * std).astype(jnp.float32),
        "wv": (jax.random.normal(ks[2], (d, kv, hd)) * std).astype(jnp.float32),
        "wo": (jax.random.normal(ks[3], (h, hd, d)) * std / np.sqrt(cfg.n_layers)
               ).astype(jnp.float32),
    }
    s = {
        "wq": ("fsdp", "tp?", None), "wk": ("fsdp", "tp?", None),
        "wv": ("fsdp", "tp?", None), "wo": ("tp?", None, "fsdp"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kv, hd), jnp.float32)
        p["bv"] = jnp.zeros((kv, hd), jnp.float32)
        s["bq"] = ("tp?", None)
        s["bk"] = ("tp?", None)
        s["bv"] = ("tp?", None)
    return p, s


def _mlp_init(rng, cfg):
    ks = jax.random.split(rng, 3)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.n_experts > 0:
        e = cfg.n_experts
        std_in, std_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(f)
        p = {"router": (jax.random.normal(ks[0], (d, e)) * std_in
                        ).astype(jnp.float32),
             "w_gate": (jax.random.normal(ks[1], (e, d, f)) * std_in
                        ).astype(jnp.float32),
             "w_up": (jax.random.normal(ks[1], (e, d, f)) * std_in
                      ).astype(jnp.float32),
             "w_down": (jax.random.normal(ks[2], (e, f, d)) * std_out
                        ).astype(jnp.float32)}
        if cfg.moe_ep and e % 8 == 0:
            s = {"router": (None, None),
                 "w_gate": ("expert", "fsdp", None),
                 "w_up": ("expert", "fsdp", None),
                 "w_down": ("expert", None, "fsdp")}
        else:
            s = {"router": (None, None),
                 "w_gate": (None, "fsdp", "tp"), "w_up": (None, "fsdp", "tp"),
                 "w_down": (None, "tp", "fsdp")}
        return p, s
    if cfg.mlp == "none" or f == 0:
        return {}, {}
    p = {"w_up": dense_init(ks[0], d, f),
         "w_down": dense_init(ks[1], f, d, scale=1.0 / np.sqrt(f))}
    s = {"w_up": ("fsdp", "tp"), "w_down": ("tp", "fsdp")}
    if cfg.mlp in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[2], d, f)
        s["w_gate"] = ("fsdp", "tp")
    return p, s


def _block_init(rng, cfg, kind: str):
    """One layer's params+specs for pattern element `kind`."""
    ks = jax.random.split(rng, 6)
    p, s = {}, {}
    n1, sn1 = _norm_init(cfg)
    p["norm1"], s["norm1"] = n1, sn1
    if kind in ("global", "local"):
        p["attn"], s["attn"] = _attn_init(ks[0], cfg)
        if cfg.encoder_decoder:   # decoder blocks get cross-attn
            p["norm_x"], s["norm_x"] = _norm_init(cfg)
            p["xattn"], s["xattn"] = _attn_init(ks[1], cfg, cross=True)
        n2, sn2 = _norm_init(cfg)
        p["norm2"], s["norm2"] = n2, sn2
        p["mlp"], s["mlp"] = _mlp_init(ks[2], cfg)
        if cfg.post_norm:
            p["post1"], s["post1"] = _norm_init(cfg)
            p["post2"], s["post2"] = _norm_init(cfg)
    elif kind == "recurrent":
        p["rec"] = recurrent_block_init(ks[0], cfg.d_model, cfg.lru_width)
        s["rec"] = {"w_in_rec": ("fsdp", "tp"), "w_in_gate": ("fsdp", "tp"),
                    "conv_w": (None, "tp"), "conv_b": ("tp",),
                    "lru": {"lam": ("tp",), "w_a": (None, "tp"),
                            "b_a": ("tp",), "w_x": (None, "tp"),
                            "b_x": ("tp",)},
                    "w_out": ("tp", "fsdp")}
        n2, sn2 = _norm_init(cfg)
        p["norm2"], s["norm2"] = n2, sn2
        p["mlp"], s["mlp"] = _mlp_init(ks[2], cfg)
    elif kind == "mlstm":
        p["cell"] = mlstm_block_init(ks[0], cfg.d_model, cfg.n_heads,
                                     cfg.mlstm_proj_factor)
        s["cell"] = {k: ("fsdp", "tp?") for k in
                     ("w_up", "w_gate", "w_q", "w_k", "w_v", "w_if")}
        s["cell"]["w_down"] = ("tp?", "fsdp")
        s["cell"]["b_if"] = (None,)
        s["cell"]["ln_scale"] = ("tp?",)
    elif kind == "slstm":
        p["cell"] = slstm_block_init(ks[0], cfg.d_model, cfg.n_heads)
        s["cell"] = {"w": ("fsdp", "tp?"), "r": (None, None, None),
                     "b": (None,), "ln_scale": (None,),
                     "w_up": ("fsdp", "tp?"), "w_gate": ("fsdp", "tp?"),
                     "w_down": ("tp?", "fsdp")}
    else:
        raise ValueError(kind)
    return p, s


def _stack_specs(spec, n):
    """Prepend the scan ("layers") axis to every spec tuple."""
    return jax.tree.map(lambda t: (None,) + tuple(t), spec,
                        is_leaf=lambda t: isinstance(t, tuple))


def _stack_map(f, xs):
    """``lax.map``'s contract as an eager Python loop + stack."""
    return jax.tree.map(lambda *ys: jnp.stack(ys), *[f(x) for x in xs])


def _init_stack(rngs, cfg, pattern, convert, map_fn):
    """Blocks tree (+ specs) for len(rngs) layers cycling `pattern`:
    whole pattern periods stacked under "scan" (``map_fn`` over the
    periods' keys), the remainder as "rest{r}".  `convert` maps each
    block's params as they are made."""
    npat = len(pattern)
    n_groups, rem = len(rngs) // npat, len(rngs) % npat
    bp, bs = {}, {}
    if n_groups:
        gspec = {}

        def group(keys):
            gp = {}
            for j, kind in enumerate(pattern):
                p, gspec[f"b{j}"] = _block_init(keys[j], cfg, kind)
                gp[f"b{j}"] = convert(p)
            return gp

        keys = rngs[:n_groups * npat]
        bp["scan"] = map_fn(
            group, keys.reshape((n_groups, npat) + keys.shape[1:]))
        bs["scan"] = _stack_specs(gspec, n_groups)
    for r in range(rem):
        p, bs[f"rest{r}"] = _block_init(rngs[n_groups * npat + r], cfg,
                                        pattern[r % npat])
        bp[f"rest{r}"] = convert(p)
    return bp, bs


def init_lm(rng, cfg: ModelConfig):
    """Returns (params, logical_specs)."""
    return _init_lm(rng, cfg, lambda p: p, _stack_map)


def _init_lm(rng, cfg, convert, map_fn):
    ks = jax.random.split(rng, 8)
    params: Params = {}
    specs: Params = {}
    params["embed"] = embed_init(ks[0], cfg.vocab_size, cfg.d_model)
    specs["embed"] = ("vocab", "fsdp")
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size)
        specs["lm_head"] = ("fsdp", "vocab")
    params["blocks"], specs["blocks"] = _init_stack(
        jax.random.split(ks[2], cfg.n_layers), cfg, cfg.pattern, convert,
        map_fn)
    fn, fs = _norm_init(cfg)
    params["final_norm"], specs["final_norm"] = fn, fs

    if cfg.encoder_decoder:
        # encoder: non-causal global attention blocks (no cross-attn)
        enc_cfg = dataclasses.replace(cfg, encoder_decoder=False)
        params["encoder"], specs["encoder"] = _init_stack(
            jax.random.split(ks[3], cfg.n_enc_layers), enc_cfg,
            ("global",), convert, map_fn)
        en, esn = _norm_init(cfg)
        params["enc_norm"], specs["enc_norm"] = en, esn
        params["enc_pos"] = (jax.random.normal(ks[4], (cfg.max_positions,
                                                       cfg.d_model)) * 0.02
                             ).astype(jnp.float32)
        specs["enc_pos"] = (None, "fsdp")
    if cfg.norm == "ln":   # whisper-style learned positions for the decoder
        params["dec_pos"] = (jax.random.normal(ks[5], (cfg.max_positions,
                                                       cfg.d_model)) * 0.02
                             ).astype(jnp.float32)
        specs["dec_pos"] = (None, "fsdp")
    return params, specs


# ---------------------------------------------------------------------------
# one-time weight quantization (serving)
# ---------------------------------------------------------------------------

def _vmapped_quantize(a, base_ndim: int):
    """Per-channel quantize of the trailing `base_ndim` dims, vmapped
    over any leading (scan-stacked layer) dims.

    CONTRACT: for stacked inputs the result is a *container* QTensor —
    values (L, ..., C) with scale (L, C) — whose aux `axis` refers to
    the UNSTACKED per-layer layout (axis = base_ndim - 1); lax.scan /
    per-layer slicing / QTensor.take reduce each leaf back to the
    per-layer shape, and QTensor.dequantize/reshape understand the
    stacked layout directly (scale.ndim - 1 leading dims are stacked)."""
    f = lambda w: quantize(w, axis=w.ndim - 1)
    for _ in range(a.ndim - base_ndim):
        f = jax.vmap(f)
    return f(a)


# the MLP/MoE-bank weight names quantize_lm_params converts — shared
# with the spec transform so the two cannot drift key-by-key
_QUANT_MLP_KEYS = ("w_up", "w_gate", "w_down")


def _map_quantized_nodes(tree, conv_attn, conv_mlp):
    """The ONE walk over the GEMM-weight nodes the serving path
    quantizes: 'attn'/'xattn' subtrees through `conv_attn`, 'mlp'
    subtrees through `conv_mlp`, every other node untouched, rooted at
    the 'blocks'/'encoder' subtrees.  Both ``quantize_lm_params`` (leaf
    converter: float array -> QTensor) and ``quantize_lm_specs`` (leaf
    converter: spec tuple -> QTensor spec node) run THIS walk, so the
    params tree and its placement-spec tree cannot structurally drift —
    a converted node in one is a converted node in the other."""
    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if k in ("attn", "xattn"):
                out[k] = conv_attn(v)
            elif k == "mlp":
                out[k] = conv_mlp(v)
            else:
                out[k] = walk(v)
        return out

    new = dict(tree)
    for key in ("blocks", "encoder"):
        if key in tree:
            new[key] = walk(tree[key])
    return new


def _qtensor_spec(values_spec):
    """Logical-spec node for one QTensor leaf produced by
    ``_vmapped_quantize``: the node's keys are the CHILD INDICES of
    ``QTensor.tree_flatten`` — 0 = ``values`` (keeps `values_spec`),
    1 = ``scale`` (the stacked leading axes plus the out-channel dim:
    scale shape is ``values.shape[:-2] + (values.shape[-1],)``).
    ``dist.sharding.Mapping.shardings`` walks pytree paths by key, and a
    registered pytree node's children are addressed by flattened index,
    so an int-keyed dict is exactly the addressable spec node."""
    values_spec = tuple(values_spec)
    return {0: values_spec, 1: values_spec[:-2] + (values_spec[-1],)}


def quantize_lm_specs(specs, cfg: ModelConfig):
    """Transform an ``init_lm`` logical-spec tree to match the params
    tree ``quantize_lm_params`` produces, so quantized serving params
    remain PLACEABLE by logical specs (multi-host sharded serving,
    DESIGN.md §8).

    The spec transform mirrors the param transform exactly:

      * attention ``wq``/``wk``/``wv`` collapse (d, H, hd) into the 2D
        GEMM layout (d, H*hd) — the merged output dim inherits the head
        dim's axis (``"tp?"``: H*hd is divisible by the TP size whenever
        H is, and the divisibility re-check at mapping time drops it
        safely when not);
      * ``wo`` collapses (H, hd, d) into (H*hd, d) the same way;
      * MLP / MoE-bank mats keep their layout (the expert axis is just a
        leading stacked dim), so their values spec is unchanged;
      * every quantized leaf becomes a ``{values, scale}`` QTensor node
        (``_qtensor_spec``): the per-output-channel scale is sharded
        like the output dim it scales.

    Leaves ``quantize_lm_params`` leaves float (embed, lm_head, norms,
    router, recurrent cells, biases) pass through untouched."""
    def merge(a, b):
        return a if a is not None else b

    def conv_attn(s):
        out = dict(s)
        for key in ("wq", "wk", "wv"):
            if key in s:
                t = tuple(s[key])
                out[key] = _qtensor_spec(t[:-2] + (merge(t[-2], t[-1]),))
        if "wo" in s:
            t = tuple(s["wo"])
            out["wo"] = _qtensor_spec(t[:-3] + (merge(t[-3], t[-2]),
                                                t[-1]))
        return out

    def conv_mlp(s):
        if not s:
            return s
        out = dict(s)
        for key in _QUANT_MLP_KEYS:
            if key in s:
                out[key] = _qtensor_spec(s[key])
        return out

    return _map_quantized_nodes(specs, conv_attn, conv_mlp)


def _quantize_attn(d):
    out = dict(d)
    for key in ("wq", "wk", "wv"):
        if key in d and not isinstance(d[key], QTensor):
            a = d[key]
            lead = a.ndim - 3
            a2 = a.reshape(a.shape[:lead + 1] + (-1,))
            out[key] = _vmapped_quantize(a2, 2)
    if "wo" in d and not isinstance(d["wo"], QTensor):
        a = d["wo"]
        lead = a.ndim - 3
        a2 = a.reshape(a.shape[:lead] + (-1, a.shape[-1]))
        out["wo"] = _vmapped_quantize(a2, 2)
    return out


def _quantize_mlp(d):
    if not d:
        return d
    out = dict(d)
    for key in _QUANT_MLP_KEYS:
        if key in d and not isinstance(d[key], QTensor):
            # expert tensors (E, in, out) vmap into stacked banks with
            # (E, out) scales; dense mats quantize in place — same code
            # path, the expert axis is just one more leading dim
            out[key] = _vmapped_quantize(d[key], 2)
    return out


def quantize_lm_params(params, cfg: ModelConfig):
    """Pre-quantize every GEMM weight that flows through ``dense`` into
    a QTensor ONCE — the serving engine calls this at init so no decode
    step re-runs weight abs-max/round/cast inside the traced graph
    (previously every dense call re-quantized its float weight).

    Attention projections are stored in their 2D GEMM layout
    ((d, H*hd) / (H*hd, d)) with per-output-channel scales — exactly the
    arrays the per-call ``quantize(w, axis=1)`` produced, so numerics are
    unchanged.  Dense-MLP mats quantize per-channel in place.  MoE expert
    mats quantize into stacked (E, in, out) QTensor BANKS with (E, out)
    per-expert per-output-channel scales — the layout the grouped expert
    kernel consumes directly (DESIGN.md §4) and bit-identical to
    ``moe.quantize_expert_bank`` applied per trace, so pre-quantizing
    kills the per-call expert requantize without changing a bit.  The
    router and recurrent cells keep per-call quantization.  Returns a
    new params tree; embed/lm_head/norms stay float.  Weights that are
    already QTensors (``init_serving_lm``) pass through, so the call is
    idempotent.
    """
    return _map_quantized_nodes(params, _quantize_attn, _quantize_mlp)


def init_serving_lm(rng, cfg: ModelConfig):
    """Serving params for random weights, without the float model.

    ``quantize_lm_params(init_lm(rng, cfg)[0], cfg)`` and ``init_lm``'s
    specs, as ONE jitted program that makes a layer group's float
    weights, quantizes them and keeps only the QTensors (a ``lax.map``
    over the groups), so the whole float tree (12 GB for a 3B model,
    more than a 16 GB chip holds beside its int8 copy) is never
    resident.  Compiled, the per-channel scales may differ from the
    eager ``quantize_lm_params`` in the last bit (XLA turns the
    constant division into a reciprocal multiply).  The specs keep the
    float layout, which ``Engine`` transforms itself
    (``quantize_lm_specs``)."""
    box = {}

    def quantize_block(p):
        return _map_quantized_nodes({"blocks": p}, _quantize_attn,
                                    _quantize_mlp)["blocks"]

    def build(rng):
        params, box["specs"] = _init_lm(rng, cfg, quantize_block,
                                        jax.lax.map)
        return params

    params = jax.jit(build)(rng)
    return params, box["specs"]


# ---------------------------------------------------------------------------
# forward blocks
# ---------------------------------------------------------------------------

def _dense_kw(cfg) -> dict:
    """dense() kwargs for the model's MAC backend (empty = XLA default)."""
    if cfg is None or cfg.mac_backend == "xla":
        return {}
    return {"backend": cfg.mac_backend, "interpret": cfg.mac_interpret,
            "block_shapes": tuple(cfg.mac_blocks)}


def _proj(x, w, approx_cfg=0, bias=None, cfg=None, heads=None):
    """x: (B,S,d) @ w: (d,H,hd) -> (B,S,H,hd) through the dense knob.

    w is a float (d,H,hd) array, or a pre-quantized QTensor stored in
    its 2D GEMM layout (d, H*hd) (quantize_lm_params) — then `heads`
    supplies H for the output reshape."""
    if isinstance(w, QTensor):
        assert heads is not None, "QTensor projections need heads="
        h, hd = heads, w.values.shape[-1] // heads
        y = dense(x, w, approx_cfg=approx_cfg, **_dense_kw(cfg))
    else:
        d, h, hd = w.shape
        y = dense(x, w.reshape(d, h * hd), approx_cfg=approx_cfg,
                  **_dense_kw(cfg))
    y = y.reshape(x.shape[:-1] + (h, hd))
    if bias is not None:
        y = y + expand_left(bias.astype(y.dtype), y.ndim)
    return y


def _attn_out(y, wo, approx_cfg=0, cfg=None):
    if isinstance(wo, QTensor):
        hhd = wo.values.shape[0]
        return dense(y.reshape(y.shape[:-2] + (hhd,)), wo,
                     approx_cfg=approx_cfg, **_dense_kw(cfg))
    h, hd, d = wo.shape
    return dense(y.reshape(y.shape[:-2] + (h * hd,)), wo.reshape(h * hd, d),
                 approx_cfg=approx_cfg, **_dense_kw(cfg))


def _mlp_apply(p, x, cfg, approx_cfg=0):
    if cfg.n_experts > 0:
        b, s, d = x.shape
        # decode (single position): dropless — a dropped token would halt
        # generation quality; the buffer is tiny at s==1 anyway.
        cf = float(cfg.n_experts) if s == 1 else cfg.capacity_factor
        groups = cfg.moe_groups if (b * s) % cfg.moe_groups == 0 else 1
        y, _ = moe_ffn(x.reshape(b * s, d), p, n_experts=cfg.n_experts,
                       top_k=cfg.top_k, capacity_factor=cf,
                       n_groups=groups, act=cfg.act,
                       renormalize=cfg.renormalize, approx_cfg=approx_cfg,
                       seq_chunks=cfg.moe_seq_chunks if s > 1 else 1,
                       unroll_chunks=cfg.unroll_chunks, ep=cfg.moe_ep,
                       backend=cfg.mac_backend, interpret=cfg.mac_interpret,
                       grouped=cfg.moe_grouped)
        return y.reshape(b, s, d)
    if not p:
        return x
    kw = _dense_kw(cfg)
    act = ACT["gelu" if cfg.mlp == "geglu" else cfg.act] \
        if cfg.mlp in ("swiglu", "geglu") else ACT[cfg.act]
    if "w_gate" in p:
        h = act(dense(x, p["w_gate"], approx_cfg=approx_cfg, **kw)) \
            * dense(x, p["w_up"], approx_cfg=approx_cfg, **kw)
    else:
        h = act(dense(x, p["w_up"], approx_cfg=approx_cfg, **kw))
    return dense(h, p["w_down"], approx_cfg=approx_cfg, **kw)


def _attention_block(p, x, cfg, kind, *, positions, approx_cfg=0,
                     causal=True, enc_out=None):
    from .layers import apply_rope
    res = x
    h = _apply_norm(p["norm1"], x, cfg)
    q = _proj(h, p["attn"]["wq"], approx_cfg, p["attn"].get("bq"), cfg,
              cfg.n_heads)
    k = _proj(h, p["attn"]["wk"], approx_cfg, p["attn"].get("bk"), cfg,
              cfg.n_kv_heads)
    v = _proj(h, p["attn"]["wv"], approx_cfg, p["attn"].get("bv"), cfg,
              cfg.n_kv_heads)
    if cfg.norm == "rms":                      # rope archs
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    window = cfg.window if kind == "local" else 0
    with jax.named_scope("attention"):
        attn = chunked_attention(q, k, v, causal=causal, window=window,
                                 logit_cap=cfg.attn_softcap,
                                 scale=cfg.query_scale, q_chunk=cfg.q_chunk,
                                 unroll=cfg.unroll_chunks)
    y = _attn_out(attn, p["attn"]["wo"], approx_cfg, cfg)
    if cfg.post_norm:
        y = _apply_norm(p["post1"], y, cfg)
    x = res + y
    if enc_out is not None and "xattn" in p:
        res = x
        h = _apply_norm(p["norm_x"], x, cfg)
        q = _proj(h, p["xattn"]["wq"], approx_cfg, cfg=cfg,
                  heads=cfg.n_heads)
        k = _proj(enc_out, p["xattn"]["wk"], approx_cfg, cfg=cfg,
                  heads=cfg.n_kv_heads)
        v = _proj(enc_out, p["xattn"]["wv"], approx_cfg, cfg=cfg,
                  heads=cfg.n_kv_heads)
        with jax.named_scope("attention"):
            attn = chunked_attention(q, k, v, causal=False,
                                     q_chunk=cfg.q_chunk,
                                     unroll=cfg.unroll_chunks)
        x = res + _attn_out(attn, p["xattn"]["wo"], approx_cfg, cfg)
    res = x
    h = _apply_norm(p["norm2"], x, cfg)
    y = _mlp_apply(p["mlp"], h, cfg, approx_cfg)
    if cfg.post_norm:
        y = _apply_norm(p["post2"], y, cfg)
    return res + y


def _apply_block(p, kind, x, cfg, *, positions, approx_cfg=0, causal=True,
                 enc_out=None):
    if kind in ("global", "local"):
        return _attention_block(p, x, cfg, kind, positions=positions,
                                approx_cfg=approx_cfg, causal=causal,
                                enc_out=enc_out)
    if kind == "recurrent":
        res = x
        h = _apply_norm(p["norm1"], x, cfg)
        y, _ = recurrent_block(p["rec"], h, approx_cfg=approx_cfg,
                               dense_kw=_dense_kw(cfg))
        x = res + y
        res = x
        h = _apply_norm(p["norm2"], x, cfg)
        return res + _mlp_apply(p["mlp"], h, cfg, approx_cfg)
    if kind == "mlstm":
        res = x
        h = _apply_norm(p["norm1"], x, cfg)
        return res + mlstm_parallel(p["cell"], h, cfg.n_heads,
                                    approx_cfg=approx_cfg,
                                    q_chunk=cfg.q_chunk,
                                    unroll=cfg.unroll_chunks,
                                    dense_kw=_dense_kw(cfg))
    if kind == "slstm":
        res = x
        h = _apply_norm(p["norm1"], x, cfg)
        y, _ = slstm_scan(p["cell"], h, cfg.n_heads, approx_cfg=approx_cfg,
                          dense_kw=_dense_kw(cfg))
        return res + y
    raise ValueError(kind)


def is_per_layer_cfg(approx_cfg) -> bool:
    """True when approx_cfg is a (n_layers,) per-layer config vector, a
    (n_layers, n_groups) per-layer-per-N-block config matrix, or a
    (n_layers, n_experts, n_groups) per-layer-per-EXPERT config tensor
    (0-d arrays are uniform scalar configs, not vectors)."""
    if isinstance(approx_cfg, (jax.Array, np.ndarray)):
        return approx_cfg.ndim in (1, 2, 3)
    return isinstance(approx_cfg, (list, tuple))


def split_layer_cfgs(approx_cfg, n_scan: int, npat: int):
    """(scan_part (n_groups, npat, ...), rest_part) of a per-layer
    vector/matrix; trailing per-N-block dims ride along unchanged."""
    acfg = jnp.asarray(approx_cfg, jnp.int32)
    scan_part = (acfg[:n_scan].reshape((-1, npat) + acfg.shape[1:])
                 if n_scan else None)
    rest_part = acfg[n_scan:]
    return scan_part, rest_part


def _layer_cfg_plan(blocks, approx_cfg, npat: int):
    """The ONE place the layer->config layout is mapped onto a blocks
    tree: returns (n_groups, acfg_scan, acfg_rest).  acfg parts are None
    for a uniform (scalar) approx_cfg; callers then select per layer
    with `approx_cfg if ac is None else ac[j]` (scan) / `acfg_rest[r]`
    (rest layers).  Shared by _run_blocks, prefill, and decode_step so
    the three paths cannot drift."""
    n_groups = (jax.tree.leaves(blocks["scan"])[0].shape[0]
                if "scan" in blocks else 0)
    if is_per_layer_cfg(approx_cfg):
        acfg_scan, acfg_rest = split_layer_cfgs(approx_cfg,
                                                n_groups * npat, npat)
    else:
        acfg_scan = acfg_rest = None
    return n_groups, acfg_scan, acfg_rest


def _is_qtensor(node) -> bool:
    return isinstance(node, QTensor)


def _hoist_banks(stack):
    """(the stack with each stacked expert bank's (L, E, K, N) int8
    values replaced by None, those values in tree order)."""
    leaves, treedef = jax.tree.flatten(stack, is_leaf=_is_qtensor)
    banks = []
    for i, leaf in enumerate(leaves):
        if _is_qtensor(leaf) and leaf.values.ndim == 4 \
                and jnp.ndim(leaf.scale) == 3:
            banks.append(leaf.values)
            leaves[i] = QTensor(None, leaf.scale, leaf.axis)
    return jax.tree.unflatten(treedef, leaves), banks


def _bind_banks(gp, banks, layer):
    """One group's params with each hoisted bank as a LayerBank."""
    leaves, treedef = jax.tree.flatten(gp, is_leaf=_is_qtensor)
    it = iter(banks)
    leaves = [LayerBank(next(it), q.scale, layer, q.axis)
              if _is_qtensor(q) and q.values is None else q for q in leaves]
    return jax.tree.unflatten(treedef, leaves)


def _scan_layer_groups(fn, x, stack, *xs, scan_layers: bool = True):
    """``fn(x, (gp, *xs_g)) -> (x, y)`` over the layer groups of a
    stacked "scan" tree, through lax.scan (or an unrolled loop when not
    cfg.scan_layers); returns (x, the y's stacked).  Every scanned site
    goes through here.

    Stacked expert banks stay out of the scanned operands: the body gets
    each as a moe.LayerBank, the whole (L, E, K, N) bank plus the group
    index (the per-expert scales are scanned as usual), so an expert
    GEMM can read its layer's tiles in place rather than from a
    per-layer copy of the slice.  A stack without banks scans exactly
    as before."""
    hoisted, banks = _hoist_banks(stack)
    n = jax.tree.leaves(stack)[0].shape[0]
    if scan_layers:
        with repeated(n):
            if not banks:
                return jax.lax.scan(fn, x, (stack,) + xs)
            return jax.lax.scan(
                lambda x, t: fn(x, (_bind_banks(t[1], banks, t[0]),)
                                + t[2:]),
                x, (jnp.arange(n), hoisted) + xs)
    ys = []
    for g in range(n):
        gp = _bind_banks(jax.tree.map(lambda a: a[g], hoisted), banks, g)
        x, y = fn(x, (gp,) + jax.tree.map(lambda a: a[g], xs))
        ys.append(y)
    return x, jax.tree.map(lambda *a: jnp.stack(a), *ys)


def _run_blocks(blocks, x, cfg, *, positions, approx_cfg=0, causal=True,
                enc_out=None, pattern=None):
    pattern = pattern or cfg.pattern
    npat = len(pattern)

    from repro.dist.sharding import lsc

    # approx_cfg is a Python int (static), a traced int32 scalar (uniform
    # runtime config), or a (n_layers,) vector (per-layer runtime
    # configs, e.g. a DynamicPowerController allocation).  The vector's
    # scanned prefix rides through lax.scan alongside the layer params.
    _, acfg_scan, acfg_rest = _layer_cfg_plan(blocks, approx_cfg,
                                              npat)

    def group_body(x, gp, ac):
        for j, kind in enumerate(pattern):
            x = lsc(x, "batch", None, None)
            x = _apply_block(gp[f"b{j}"], kind, x, cfg, positions=positions,
                             approx_cfg=approx_cfg if ac is None else ac[j],
                             causal=causal, enc_out=enc_out)
        return x

    if "scan" in blocks:
        body = group_body
        if cfg.remat:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(group_body, policy=policy)
        x, _ = _scan_layer_groups(lambda c, t: (body(c, *t), None), x,
                                  blocks["scan"], acfg_scan,
                                  scan_layers=cfg.scan_layers)
    r = 0
    while f"rest{r}" in blocks:
        # rest layers follow n_groups*npat scanned layers, so their kind
        # index reduces to r % npat
        x = _apply_block(blocks[f"rest{r}"], pattern[r % npat], x, cfg,
                         positions=positions,
                         approx_cfg=(approx_cfg if acfg_rest is None
                                     else acfg_rest[r]),
                         causal=causal, enc_out=enc_out)
        r += 1
    return x


# ---------------------------------------------------------------------------
# model-level entry points
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def encode(params, cfg, enc_embeds):
    """Whisper encoder over stub frame embeddings (B, S_enc, d)."""
    from repro.dist.sharding import lsc
    enc_embeds = lsc(enc_embeds, "batch", None, None)
    x = enc_embeds.astype(cfg.compute_dtype)
    s = x.shape[1]
    x = x + params["enc_pos"][:s][None].astype(x.dtype)
    positions = jnp.arange(s)[None]
    x = _run_blocks(params["encoder"], x, cfg, positions=positions,
                    causal=False, pattern=("global",))
    return _apply_norm(params["enc_norm"], x, cfg)


def forward(params, cfg: ModelConfig, tokens, *, vision_embeds=None,
            enc_embeds=None, approx_cfg=0):
    """Full-sequence hidden states (B, S_total, d).

    approx_cfg: Python int (static), traced int32 scalar (uniform
    runtime config), a (n_layers,) per-layer config vector, or —
    pallas backend — a (n_layers, n_groups) / (n_layers, n_experts,
    n_groups) matrix (per-layer slices with an expert axis reach MoE
    experts individually; dense GEMMs collapse the expert axis to the
    lowest-measured-MRED config, see layers.dense)."""
    from repro.dist.sharding import lsc
    tokens = lsc(tokens, "batch", None)
    x = embed_tokens(params, cfg, tokens)
    x = lsc(x, "batch", None, None)
    if cfg.vision_prefix_len and vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    if cfg.norm == "ln":   # learned positions (whisper decoder)
        x = x + params["dec_pos"][:x.shape[1]][None].astype(x.dtype)
    enc_out = None
    if cfg.encoder_decoder and enc_embeds is not None:
        enc_out = encode(params, cfg, enc_embeds)
    positions = jnp.arange(x.shape[1])[None]
    x = _run_blocks(params["blocks"], x, cfg, positions=positions,
                    approx_cfg=approx_cfg, causal=True, enc_out=enc_out)
    return _apply_norm(params["final_norm"], x, cfg)


@jax.named_scope("lm_head")
def logits_for(params, cfg, hidden):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.dot(hidden, w.astype(hidden.dtype))
    if cfg.final_softcap > 0:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits


def lm_loss(params, cfg: ModelConfig, batch, *, approx_cfg=0):
    """Chunked-vocab cross entropy.  batch: tokens/labels (+ stubs).
    labels == -1 are masked (vision prefix positions etc.)."""
    hidden = forward(params, cfg, batch["tokens"],
                     vision_embeds=batch.get("vision_embeds"),
                     enc_embeds=batch.get("enc_embeds"),
                     approx_cfg=approx_cfg)
    labels = batch["labels"]
    if cfg.vision_prefix_len and batch.get("vision_embeds") is not None:
        pad = jnp.full(labels.shape[:1] + (cfg.vision_prefix_len,), -1,
                       labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    b, s, d = hidden.shape
    n_chunks = cfg.loss_chunks if s % cfg.loss_chunks == 0 else 1
    hs = hidden.reshape(b, n_chunks, s // n_chunks, d).swapaxes(0, 1)
    ls = labels.reshape(b, n_chunks, s // n_chunks).swapaxes(0, 1)

    def chunk_loss(args):
        h, l = args
        logits = logits_for(params, cfg, h).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(l, 0)[..., None],
                                   axis=-1)[..., 0]
        mask = (l >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * mask), jnp.sum(mask)

    losses, counts = jax.lax.map(chunk_loss, (hs, ls))
    return jnp.sum(losses) / jnp.maximum(jnp.sum(counts), 1.0)


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               enc_len: int = 0):
    """Cache pytree (+ logical specs) for decode.  Attention layers get
    (B, S, KV, hd) K/V buffers (ring-buffered to `window` for local
    layers); recurrent kinds get their O(1) states."""
    kinds = cfg.layer_kinds()
    npat = len(cfg.pattern)
    n_groups, rem = cfg.n_layers // npat, cfg.n_layers % npat
    kv_dt = jnp.int8 if cfg.kv_quant else cfg.compute_dtype

    def layer_cache(kind):
        if kind in ("global", "local"):
            s = min(cfg.window, max_len) if kind == "local" else max_len
            c = {"k": jnp.zeros((batch_size, s, cfg.n_kv_heads, cfg.head_dim),
                                kv_dt),
                 "v": jnp.zeros((batch_size, s, cfg.n_kv_heads, cfg.head_dim),
                                kv_dt)}
            sp = {"k": ("batch", "kv_seq", "tp?", "kv_hd"),
                  "v": ("batch", "kv_seq", "tp?", "kv_hd")}
            if cfg.kv_quant:
                c["k_s"] = jnp.zeros((batch_size, s, cfg.n_kv_heads),
                                     jnp.float32)
                c["v_s"] = jnp.zeros((batch_size, s, cfg.n_kv_heads),
                                     jnp.float32)
                sp["k_s"] = ("batch", "kv_seq", "tp?")
                sp["v_s"] = ("batch", "kv_seq", "tp?")
            if cfg.encoder_decoder:
                c["xk"] = jnp.zeros((batch_size, enc_len, cfg.n_kv_heads,
                                     cfg.head_dim), cfg.compute_dtype)
                c["xv"] = jnp.zeros_like(c["xk"])
                sp["xk"] = ("batch", None, "tp?", None)
                sp["xv"] = ("batch", None, "tp?", None)
            return c, sp
        if kind == "recurrent":
            kw = 4  # conv width
            c = {"h": jnp.zeros((batch_size, cfg.lru_width), jnp.float32),
                 "conv": jnp.zeros((batch_size, kw - 1, cfg.lru_width),
                                   jnp.float32)}
            sp = {"h": ("batch", "tp"), "conv": ("batch", None, "tp")}
            return c, sp
        if kind == "mlstm":
            d_inner = int(cfg.d_model * cfg.mlstm_proj_factor)
            hd = d_inner // cfg.n_heads
            c = {"C": jnp.zeros((batch_size, cfg.n_heads, hd, hd), jnp.float32),
                 "n": jnp.zeros((batch_size, cfg.n_heads, hd), jnp.float32),
                 "m": jnp.full((batch_size, cfg.n_heads), -30.0, jnp.float32)}
            sp = {"C": ("batch", "tp?", None, None),
                  "n": ("batch", "tp?", None), "m": ("batch", "tp?")}
            return c, sp
        if kind == "slstm":
            z = jnp.zeros((batch_size, cfg.d_model), jnp.float32)
            c = {"h": z, "c": z, "n": z, "m": z - 30.0}
            sp = {k: ("batch", None) for k in "hcnm"}
            return c, sp
        raise ValueError(kind)

    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    cspec: Params = {"pos": ()}
    if n_groups:
        gc, gs = {}, {}
        for j in range(npat):
            c, sp = layer_cache(cfg.pattern[j])
            gc[f"b{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape).copy(), c)
            gs[f"b{j}"] = jax.tree.map(
                lambda t: (None,) + tuple(t), sp,
                is_leaf=lambda t: isinstance(t, tuple))
        cache["scan"], cspec["scan"] = gc, gs
    for r in range(rem):
        c, sp = layer_cache(cfg.pattern[r % npat])
        cache[f"rest{r}"], cspec[f"rest{r}"] = c, sp
    return cache, cspec


def _kv_write(cache_layer, kind, k_new, v_new, pos, cfg, window):
    """Write K/V at `pos` (ring-buffered for local).

    kv_onehot_write (single-token writes only): express the update as a
    one-hot masked blend instead of dynamic-update-slice.  On a cache
    whose sequence dim is sharded, DUS at a traced index forces GSPMD to
    all-gather the cache every step; the blend stays shard-local at the
    cost of re-writing the cache (decode is cache-bandwidth-bound anyway
    — §Perf iteration 1)."""
    s_buf = cache_layer["k"].shape[1]
    idx = pos % s_buf
    if cfg.kv_onehot_write and k_new.shape[1] == 1:
        oh = (jnp.arange(s_buf) == idx)[None, :, None, None]

        def blend(buf, val):
            val = val.astype(jnp.float32) if buf.dtype == jnp.int8 else val
            out = jnp.where(oh, val.astype(jnp.float32),
                            buf.astype(jnp.float32))
            return out.astype(buf.dtype)

        cache_layer = dict(cache_layer)
        if cfg.kv_quant:
            def q8(x):
                sc = jnp.max(jnp.abs(x), axis=-1) / 127.0 + 1e-9
                qv = jnp.clip(jnp.round(x / sc[..., None]), -127, 127
                              ).astype(jnp.int8)
                return qv, sc
            kq, ks = q8(k_new.astype(jnp.float32))
            vq, vs = q8(v_new.astype(jnp.float32))
            oh3 = oh[..., 0]
            cache_layer["k"] = jnp.where(oh, kq, cache_layer["k"])
            cache_layer["v"] = jnp.where(oh, vq, cache_layer["v"])
            cache_layer["k_s"] = jnp.where(oh3, ks, cache_layer["k_s"])
            cache_layer["v_s"] = jnp.where(oh3, vs, cache_layer["v_s"])
            return cache_layer
        cache_layer["k"] = blend(cache_layer["k"], k_new)
        cache_layer["v"] = blend(cache_layer["v"], v_new)
        return cache_layer
    if cfg.kv_quant:
        def q8(x):
            s = jnp.max(jnp.abs(x), axis=-1) / 127.0 + 1e-9   # (B,1,KV)
            q = jnp.clip(jnp.round(x / s[..., None]), -127, 127
                         ).astype(jnp.int8)
            return q, s
        kq, ks = q8(k_new.astype(jnp.float32))
        vq, vs = q8(v_new.astype(jnp.float32))
        cache_layer = dict(cache_layer)
        cache_layer["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache_layer["k"], kq, idx, axis=1)
        cache_layer["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache_layer["v"], vq, idx, axis=1)
        cache_layer["k_s"] = jax.lax.dynamic_update_slice_in_dim(
            cache_layer["k_s"], ks, idx, axis=1)
        cache_layer["v_s"] = jax.lax.dynamic_update_slice_in_dim(
            cache_layer["v_s"], vs, idx, axis=1)
        return cache_layer
    cache_layer = dict(cache_layer)
    cache_layer["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache_layer["k"], k_new.astype(cache_layer["k"].dtype), idx, axis=1)
    cache_layer["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache_layer["v"], v_new.astype(cache_layer["v"].dtype), idx, axis=1)
    return cache_layer


def _kv_read(cache_layer, cfg):
    if cfg.kv_quant:
        k = (cache_layer["k"].astype(jnp.float32)
             * cache_layer["k_s"][..., None]).astype(cfg.compute_dtype)
        v = (cache_layer["v"].astype(jnp.float32)
             * cache_layer["v_s"][..., None]).astype(cfg.compute_dtype)
        return k, v
    return cache_layer["k"], cache_layer["v"]


def _decode_block(p, kind, x_t, cl, cfg, pos, *, approx_cfg=0):
    """One layer, one token. x_t: (B,1,d). Returns (x_t, new_cache_layer)."""
    from .layers import apply_rope
    if kind in ("global", "local"):
        res = x_t
        h = _apply_norm(p["norm1"], x_t, cfg)
        q = _proj(h, p["attn"]["wq"], approx_cfg, p["attn"].get("bq"), cfg,
                  cfg.n_heads)
        k = _proj(h, p["attn"]["wk"], approx_cfg, p["attn"].get("bk"), cfg,
                  cfg.n_kv_heads)
        v = _proj(h, p["attn"]["wv"], approx_cfg, p["attn"].get("bv"), cfg,
                  cfg.n_kv_heads)
        if cfg.norm == "rms":
            posv = pos[None, None] if pos.ndim == 0 else pos[:, None]
            q = apply_rope(q, posv, cfg.rope_theta)
            k = apply_rope(k, posv, cfg.rope_theta)
        window = cfg.window if kind == "local" else 0
        if cfg.kv_onehot_write:
            # seq-sharded cache mode: replicate q over the model axis so
            # the score einsum's only sharded free dim is the cache seq —
            # otherwise GSPMD all-gathers the (GB-scale) cache instead of
            # the (KB-scale) query (§Perf iteration 1, second attempt).
            from repro.dist.sharding import lsc
            q = lsc(q, "batch", None, None, None)
        cl = _kv_write(cl, kind, k, v, pos, cfg, window)
        kc, vc = _kv_read(cl, cfg)
        s_buf = kc.shape[1]
        cache_len = jnp.minimum(pos + 1, s_buf)
        attn = decode_attention(q, kc, vc, cache_len,
                                window=0 if kind == "local" else 0,
                                logit_cap=cfg.attn_softcap,
                                scale=cfg.query_scale)
        if cfg.kv_onehot_write:
            # block backward propagation of wo's head-sharding into the
            # score tensors (it would re-gather the seq-sharded cache)
            from repro.dist.sharding import lsc
            attn = lsc(attn, "batch", None, None, None)
        y = _attn_out(attn, p["attn"]["wo"], approx_cfg, cfg)
        if cfg.post_norm:
            y = _apply_norm(p["post1"], y, cfg)
        x_t = res + y
        if cfg.encoder_decoder and "xattn" in p:
            res = x_t
            h = _apply_norm(p["norm_x"], x_t, cfg)
            q = _proj(h, p["xattn"]["wq"], approx_cfg, cfg=cfg,
                      heads=cfg.n_heads)
            attn = decode_attention(q, cl["xk"], cl["xv"],
                                    cl["xk"].shape[1])
            x_t = res + _attn_out(attn, p["xattn"]["wo"], approx_cfg, cfg)
        res = x_t
        h = _apply_norm(p["norm2"], x_t, cfg)
        y = _mlp_apply(p["mlp"], h, cfg, approx_cfg)
        if cfg.post_norm:
            y = _apply_norm(p["post2"], y, cfg)
        return res + y, cl
    if kind == "recurrent":
        res = x_t
        h = _apply_norm(p["norm1"], x_t, cfg)
        y, new_state = recurrent_block(p["rec"], h, approx_cfg=approx_cfg,
                                       state=cl, decode=True,
                                       dense_kw=_dense_kw(cfg))
        x_t = res + y
        res = x_t
        h = _apply_norm(p["norm2"], x_t, cfg)
        return res + _mlp_apply(p["mlp"], h, cfg, approx_cfg), new_state
    if kind == "mlstm":
        res = x_t
        h = _apply_norm(p["norm1"], x_t, cfg)
        y, new_state = mlstm_step(p["cell"], h, cl, cfg.n_heads,
                                  approx_cfg=approx_cfg,
                                  dense_kw=_dense_kw(cfg))
        return res + y, new_state
    if kind == "slstm":
        res = x_t
        h = _apply_norm(p["norm1"], x_t, cfg)
        y, new_state = slstm_step(p["cell"], h, cl, cfg.n_heads,
                                  approx_cfg=approx_cfg,
                                  dense_kw=_dense_kw(cfg))
        return res + y, new_state
    raise ValueError(kind)


def decode_step(params, cfg: ModelConfig, cache, token, *,
                approx_cfg=0):
    """token: (B, 1) int32 -> (logits (B, V), new_cache).

    approx_cfg: Python int, traced int32 scalar, or per-layer
    (n_layers,) vector — see _run_blocks."""
    from repro.dist.sharding import lsc
    token = lsc(token, "batch", None)
    x = embed_tokens(params, cfg, token)
    x = lsc(x, "batch", None, None)
    if cfg.norm == "ln":
        x = x + params["dec_pos"][cache["pos"]][None, None].astype(x.dtype)
    pos = cache["pos"]
    new_cache: Params = {"pos": pos + 1}

    npat = len(cfg.pattern)
    _, acfg_scan, acfg_rest = _layer_cfg_plan(params["blocks"],
                                              approx_cfg, npat)

    if "scan" in params["blocks"]:
        def scan_fn(x, gp_cl_ac):
            gp, cl, ac = gp_cl_ac
            ncl = {}
            for j, kind in enumerate(cfg.pattern):
                x = lsc(x, "batch", None, None)
                x, c = _decode_block(
                    gp[f"b{j}"], kind, x, cl[f"b{j}"], cfg, pos,
                    approx_cfg=approx_cfg if ac is None else ac[j])
                ncl[f"b{j}"] = c
            return x, ncl
        x, new_cache["scan"] = _scan_layer_groups(
            scan_fn, x, params["blocks"]["scan"], cache["scan"], acfg_scan,
            scan_layers=cfg.scan_layers)
    r = 0
    while f"rest{r}" in params["blocks"]:
        kind = cfg.pattern[r % len(cfg.pattern)]
        x, c = _decode_block(params["blocks"][f"rest{r}"], kind, x,
                             cache[f"rest{r}"], cfg, pos,
                             approx_cfg=(approx_cfg if acfg_rest is None
                                         else acfg_rest[r]))
        new_cache[f"rest{r}"] = c
        r += 1
    x = _apply_norm(params["final_norm"], x, cfg)
    logits = logits_for(params, cfg, x[:, 0])
    return logits, new_cache


# ---------------------------------------------------------------------------
# serving: speculative verify (DESIGN.md §12)
# ---------------------------------------------------------------------------

def verify_gate(cfg: ModelConfig):
    """Speculative draft/verify covers the same model family as the
    paged cache: all-'global' attention, float KV, decoder-only."""
    if any(k != "global" for k in cfg.layer_kinds()):
        raise ValueError("speculative verify needs an all-'global' "
                         "pattern")
    if cfg.kv_quant or cfg.kv_onehot_write:
        raise ValueError("speculative verify is float-KV only (no "
                         "kv_quant / kv_onehot_write)")
    if cfg.encoder_decoder or cfg.vision_prefix_len:
        raise ValueError("speculative verify does not cover "
                         "encoder-decoder or vision-prefix models")


def _verify_block(p, x, cl, cfg, positions, *, approx_cfg=0):
    """One all-'global' layer over a W-token verify window against the
    dense cache.  x: (B,W,d); cl: the layer's (B,S,KV,hd) K/V buffers;
    positions: (W,) traced absolute entries of the window tokens.  The
    window's K/V scatter into entries positions[w] (rows past the
    buffer end drop — scatter, not dynamic-update-slice, so a clipped
    tail can never shift the whole window), then every window position
    attends causally over the full updated buffer."""
    from .attention import NEG_INF, _repeat_kv
    from .layers import apply_rope
    res = x
    h = _apply_norm(p["norm1"], x, cfg)
    q = _proj(h, p["attn"]["wq"], approx_cfg, p["attn"].get("bq"), cfg,
              cfg.n_heads)
    k = _proj(h, p["attn"]["wk"], approx_cfg, p["attn"].get("bk"), cfg,
              cfg.n_kv_heads)
    v = _proj(h, p["attn"]["wv"], approx_cfg, p["attn"].get("bv"), cfg,
              cfg.n_kv_heads)
    if cfg.norm == "rms":
        q = apply_rope(q, positions[None], cfg.rope_theta)
        k = apply_rope(k, positions[None], cfg.rope_theta)
    cl = dict(cl)
    cl["k"] = cl["k"].at[:, positions].set(k.astype(cl["k"].dtype))
    cl["v"] = cl["v"].at[:, positions].set(v.astype(cl["v"].dtype))
    kc, vc = cl["k"], cl["v"]
    k_r = _repeat_kv(kc, cfg.n_heads // cfg.n_kv_heads)
    v_r = _repeat_kv(vc, cfg.n_heads // cfg.n_kv_heads)
    scale = (cfg.query_scale if cfg.query_scale is not None
             else cfg.head_dim ** -0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_r.astype(jnp.float32)) * scale
    if cfg.attn_softcap > 0:
        scores = softcap(scores, cfg.attn_softcap)
    key_pos = jnp.arange(kc.shape[1])
    valid = key_pos[None, :] <= positions[:, None]        # (W, S) causal
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", w,
                      v_r.astype(jnp.float32)).astype(q.dtype)
    y = _attn_out(attn, p["attn"]["wo"], approx_cfg, cfg)
    if cfg.post_norm:
        y = _apply_norm(p["post1"], y, cfg)
    x = res + y
    res = x
    h = _apply_norm(p["norm2"], x, cfg)
    y = _mlp_apply(p["mlp"], h, cfg, approx_cfg)
    if cfg.post_norm:
        y = _apply_norm(p["post2"], y, cfg)
    return res + y, cl


def decode_verify(params, cfg: ModelConfig, cache, tokens, pos, *,
                  approx_cfg=0):
    """Score a W-token window in ONE pass against the dense cache — the
    speculative-decoding verify step (DESIGN.md §12).

    tokens: (B, W) int32 — row b holds ``[pending_input, draft_1 ..
    draft_k]`` right-padded to the STATIC window W (= max_k + 1; the
    live draft depth k only changes how many rows the host reads, so
    every (k, draft-config) pair shares this one executable — the
    zero-retrace invariant).  pos: traced int32 scalar, the absolute
    cache entry of tokens[:, 0] (the dense pool position).  The
    window's K/V are computed at THIS call's config and overwrite
    whatever the draft steps left at entries pos..pos+W-1; row w of the
    returned (B, W, V) logits scores position pos+w.  Rows past the
    valid count depend only on pad tokens: their logits are ignored
    and their K/V writes land past the committed length, masked by the
    pool position and rewritten before any read."""
    verify_gate(cfg)
    W = tokens.shape[1]
    positions = pos + jnp.arange(W)
    x = embed_tokens(params, cfg, tokens)
    if cfg.norm == "ln":
        x = x + jnp.take(params["dec_pos"], positions, axis=0
                         )[None].astype(x.dtype)
    new_cache: Params = {"pos": jnp.asarray(pos) + W}
    npat = len(cfg.pattern)
    _, acfg_scan, acfg_rest = _layer_cfg_plan(params["blocks"],
                                              approx_cfg, npat)
    if "scan" in params["blocks"]:
        def scan_fn(x, gp_cl_ac):
            gp, cl, ac = gp_cl_ac
            ncl = {}
            for j in range(npat):
                x, c = _verify_block(
                    gp[f"b{j}"], x, cl[f"b{j}"], cfg, positions,
                    approx_cfg=approx_cfg if ac is None else ac[j])
                ncl[f"b{j}"] = c
            return x, ncl
        x, new_cache["scan"] = _scan_layer_groups(
            scan_fn, x, params["blocks"]["scan"], cache["scan"], acfg_scan,
            scan_layers=cfg.scan_layers)
    r = 0
    while f"rest{r}" in params["blocks"]:
        x, c = _verify_block(params["blocks"][f"rest{r}"], x,
                             cache[f"rest{r}"], cfg, positions,
                             approx_cfg=(approx_cfg if acfg_rest is None
                                         else acfg_rest[r]))
        new_cache[f"rest{r}"] = c
        r += 1
    x = _apply_norm(params["final_norm"], x, cfg)
    logits = logits_for(params, cfg, x)
    return logits, new_cache


def prefill(params, cfg: ModelConfig, tokens, *, vision_embeds=None,
            enc_embeds=None, max_len: int | None = None,
            approx_cfg=0, true_len=None):
    """Sequence prefill: returns (last-token logits, populated cache).

    Implementation: full forward for activations; K/V recomputed per
    layer into the cache via a per-layer pass (keeps code simple and
    XLA CSEs the shared projections).

    ``true_len`` (traced int32 scalar) marks the real prompt length
    inside right-padded ``tokens`` so ONE compiled executable serves
    every prompt length up to the pad boundary (the engine pads to
    ``prefill_pad``): K/V writes beyond ``true_len`` are zeroed and the
    returned logits come from position ``true_len - 1``.  Causality
    makes every position < true_len blind to the pad tokens, so the
    result is bit-identical to an unpadded prefill of length true_len.
    Attention-only patterns (recurrent states would scan the pads) and
    float KV caches only (int8 would stamp nonzero scales on pads)."""
    if true_len is not None:
        if not all(k in ("global", "local") for k in cfg.layer_kinds()):
            raise ValueError("true_len= needs an attention-only pattern")
        if cfg.kv_quant or cfg.vision_prefix_len or cfg.encoder_decoder:
            raise ValueError("true_len= is incompatible with kv_quant / "
                             "vision prefixes / encoder-decoder")
    b, s = tokens.shape[0], tokens.shape[1]
    if cfg.vision_prefix_len and vision_embeds is not None:
        s = s + cfg.vision_prefix_len
    max_len = max_len or s
    enc_len = enc_embeds.shape[1] if enc_embeds is not None else 0
    cache, cache_spec = init_cache(cfg, b, max_len, enc_len)
    from repro.dist.sharding import lsc, lsc_tree
    cache = lsc_tree(cache, cache_spec)
    tokens = lsc(tokens, "batch", None)
    x = embed_tokens(params, cfg, tokens)
    x = lsc(x, "batch", None, None)
    if cfg.vision_prefix_len and vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    if cfg.norm == "ln":
        x = x + params["dec_pos"][:x.shape[1]][None].astype(x.dtype)
    enc_out = None
    if cfg.encoder_decoder and enc_embeds is not None:
        enc_out = encode(params, cfg, enc_embeds)
    positions = jnp.arange(x.shape[1])[None]

    npat = len(cfg.pattern)
    _, acfg_scan, acfg_rest = _layer_cfg_plan(params["blocks"],
                                              approx_cfg, npat)

    def fill_block(p, kind, x, cl, approx_cfg=approx_cfg):
        from .layers import apply_rope
        x = lsc(x, "batch", None, None)
        if kind in ("global", "local"):
            h = _apply_norm(p["norm1"], x, cfg)
            k = _proj(h, p["attn"]["wk"], approx_cfg, p["attn"].get("bk"),
                      cfg, cfg.n_kv_heads)
            v = _proj(h, p["attn"]["wv"], approx_cfg, p["attn"].get("bv"),
                      cfg, cfg.n_kv_heads)
            if cfg.norm == "rms":
                k = apply_rope(k, positions, cfg.rope_theta)
            s_buf = cl["k"].shape[1]
            k_w = k[:, -s_buf:]
            v_w = v[:, -s_buf:]
            if true_len is not None:
                # zero the pad positions so the cache matches what an
                # unpadded prefill of length true_len would hold
                pad_mask = (jnp.arange(k_w.shape[1])[None]
                            < jnp.reshape(true_len, (1, 1)))
                k_w = k_w * pad_mask[:, :, None, None].astype(k_w.dtype)
                v_w = v_w * pad_mask[:, :, None, None].astype(v_w.dtype)
            with jax.named_scope("attention"):
                cl = _kv_write(cl, kind, k_w, v_w, jnp.zeros((), jnp.int32),
                               cfg, cfg.window)
                if kind == "local" and x.shape[1] > s_buf:
                    # ring-buffer invariant: position p lives at index
                    # p % s_buf.  prefill wrote positions [S-s_buf, S) at
                    # [0, s_buf); roll so decode's pos % s_buf indexing
                    # lines up.
                    roll = (x.shape[1] - s_buf) % s_buf
                    cl = {kk: (jnp.roll(vv, roll, axis=1)
                               if kk in ("k", "v", "k_s", "v_s") else vv)
                          for kk, vv in cl.items()}
            if cfg.encoder_decoder and "xattn" in p:
                cl = dict(cl)
                cl["xk"] = _proj(enc_out, p["xattn"]["wk"], approx_cfg,
                                 cfg=cfg, heads=cfg.n_kv_heads
                                 ).astype(cl["xk"].dtype)
                cl["xv"] = _proj(enc_out, p["xattn"]["wv"], approx_cfg,
                                 cfg=cfg, heads=cfg.n_kv_heads
                                 ).astype(cl["xv"].dtype)
            x = _apply_block(p, kind, x, cfg, positions=positions,
                             approx_cfg=approx_cfg, causal=True,
                             enc_out=enc_out)
            return x, cl
        # recurrent kinds: run the parallel path, capture final state
        if kind == "recurrent":
            res = x
            h = _apply_norm(p["norm1"], x, cfg)
            y, state = recurrent_block(p["rec"], h, approx_cfg=approx_cfg,
                                       dense_kw=_dense_kw(cfg))
            x = res + y
            res = x
            h = _apply_norm(p["norm2"], x, cfg)
            return res + _mlp_apply(p["mlp"], h, cfg, approx_cfg), state
        if kind == "mlstm":
            from .recurrent import mlstm_final_state
            res = x
            h = _apply_norm(p["norm1"], x, cfg)
            y = mlstm_parallel(p["cell"], h, cfg.n_heads,
                               approx_cfg=approx_cfg, q_chunk=cfg.q_chunk,
                               unroll=cfg.unroll_chunks,
                               dense_kw=_dense_kw(cfg))
            state = mlstm_final_state(p["cell"], h, cfg.n_heads,
                                      approx_cfg=approx_cfg,
                                      dense_kw=_dense_kw(cfg))
            return res + y, state
        if kind == "slstm":
            res = x
            h = _apply_norm(p["norm1"], x, cfg)
            y, state = slstm_scan(p["cell"], h, cfg.n_heads,
                                  approx_cfg=approx_cfg,
                                  dense_kw=_dense_kw(cfg))
            return res + y, state
        raise ValueError(kind)

    new_cache: Params = {"pos": (jnp.asarray(s, jnp.int32) if true_len is None
                                 else jnp.asarray(true_len, jnp.int32))}
    if "scan" in params["blocks"]:
        def scan_fn(x, gp_cl_ac):
            gp, cl, ac = gp_cl_ac
            ncl = {}
            for j, kind in enumerate(cfg.pattern):
                x, c = fill_block(
                    gp[f"b{j}"], kind, x, cl[f"b{j}"],
                    approx_cfg=approx_cfg if ac is None else ac[j])
                ncl[f"b{j}"] = c
            return x, ncl
        x, new_cache["scan"] = _scan_layer_groups(
            scan_fn, x, params["blocks"]["scan"], cache["scan"], acfg_scan,
            scan_layers=cfg.scan_layers)
    r = 0
    while f"rest{r}" in params["blocks"]:
        kind = cfg.pattern[r % len(cfg.pattern)]
        x, c = fill_block(params["blocks"][f"rest{r}"], kind, x,
                          cache[f"rest{r}"],
                          approx_cfg=(approx_cfg if acfg_rest is None
                                      else acfg_rest[r]))
        new_cache[f"rest{r}"] = c
        r += 1
    x = _apply_norm(params["final_norm"], x, cfg)
    last = (x[:, -1] if true_len is None
            else jnp.take(x, jnp.asarray(true_len, jnp.int32) - 1, axis=1))
    logits = logits_for(params, cfg, last)
    return logits, new_cache


# ---------------------------------------------------------------------------
# serving: paged KV cache (DESIGN.md §11)
# ---------------------------------------------------------------------------
# The paged entry points replace the dense (B, S, KV, hd) cache rows with
# one (num_blocks, block_size, KV, hd) pool per layer plus per-request
# block tables.  Tables, sequence lengths and the active mask are int32
# DATA operands — never shapes — so one compiled executable serves any
# mix of stream counts and prompt lengths (the zero-retrace invariant).
# Block ids 0/1 are reserved (see serve/paged_cache.py): 0 is all-zero
# and backs unallocated table entries, 1 absorbs masked-off writes.

def _paged_gate(cfg: ModelConfig):
    if any(k != "global" for k in cfg.layer_kinds()):
        raise ValueError("paged cache needs an all-'global' pattern")
    if cfg.kv_quant or cfg.kv_onehot_write:
        raise ValueError("paged cache is float-KV only (no kv_quant / "
                         "kv_onehot_write)")
    if cfg.encoder_decoder or cfg.vision_prefix_len:
        raise ValueError("paged cache does not cover encoder-decoder or "
                         "vision-prefix models")


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int):
    """Block-pool cache pytree (+ logical specs) for paged decode.

    Per attention layer: K/V pools of shape (num_blocks, block_size,
    KV, hd) — no batch axis; requests own pool blocks through their
    block tables.  Block 0 (ZERO_BLOCK) is all-zero and must never be
    written so unowned table entries gather zeros, matching what the
    dense cache holds past ``pos``."""
    _paged_gate(cfg)
    npat = len(cfg.pattern)
    n_groups, rem = cfg.n_layers // npat, cfg.n_layers % npat

    def layer_cache():
        z = jnp.zeros((num_blocks, block_size, cfg.n_kv_heads,
                       cfg.head_dim), cfg.compute_dtype)
        return ({"k": z, "v": z},
                {"k": (None, None, "tp?", "kv_hd"),
                 "v": (None, None, "tp?", "kv_hd")})

    cache: Params = {}
    cspec: Params = {}
    if n_groups:
        gc, gs = {}, {}
        for j in range(npat):
            c, sp = layer_cache()
            gc[f"b{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape).copy(), c)
            gs[f"b{j}"] = jax.tree.map(
                lambda t: (None,) + tuple(t), sp,
                is_leaf=lambda t: isinstance(t, tuple))
        cache["scan"], cspec["scan"] = gc, gs
    for r in range(rem):
        c, sp = layer_cache()
        cache[f"rest{r}"], cspec[f"rest{r}"] = c, sp
    return cache, cspec


def _paged_attn_block(p, x_t, cl, cfg, tables, seq_lens, active, *,
                      approx_cfg=0, backend="xla"):
    """One paged layer, one token per row.  x_t: (B,1,d); cl holds the
    layer's (NB, bs, KV, hd) K/V pools; tables: (B,P) int32; seq_lens:
    (B,) int32 tokens already cached per row; active: (B,) bool."""
    from repro.serve.paged_cache import TRASH_BLOCK

    from .layers import apply_rope
    res = x_t
    h = _apply_norm(p["norm1"], x_t, cfg)
    q = _proj(h, p["attn"]["wq"], approx_cfg, p["attn"].get("bq"), cfg,
              cfg.n_heads)
    k = _proj(h, p["attn"]["wk"], approx_cfg, p["attn"].get("bk"), cfg,
              cfg.n_kv_heads)
    v = _proj(h, p["attn"]["wv"], approx_cfg, p["attn"].get("bv"), cfg,
              cfg.n_kv_heads)
    if cfg.norm == "rms":
        posv = seq_lens[:, None]
        q = apply_rope(q, posv, cfg.rope_theta)
        k = apply_rope(k, posv, cfg.rope_theta)
    with jax.named_scope("attention"):
        bs = cl["k"].shape[1]
        b_idx = jnp.arange(x_t.shape[0])
        # the current token's K/V lands in the row's tail block; inactive
        # rows scatter into the trash block (contents never read)
        write_block = jnp.where(active, tables[b_idx, seq_lens // bs],
                                TRASH_BLOCK)
        write_off = seq_lens % bs
        cl = dict(cl)
        cl["k"] = cl["k"].at[write_block, write_off].set(
            k[:, 0].astype(cl["k"].dtype))
        cl["v"] = cl["v"].at[write_block, write_off].set(
            v[:, 0].astype(cl["v"].dtype))
        cache_len = seq_lens + 1
        if backend == "pallas":
            from repro.kernels.flash_attention.paged_attention import \
                paged_decode_attention
            attn = paged_decode_attention(
                q, cl["k"], cl["v"], tables, cache_len,
                logit_cap=cfg.attn_softcap, scale=cfg.query_scale,
                interpret=cfg.mac_interpret)
        else:
            # gather-view decode: (B, P*bs, KV, hd) through the table, then
            # the stock masked decode attention (bit-identical to the dense
            # pool when P*bs matches its max_len — same shapes, same values:
            # positions >= cache_len are masked to NEG_INF either way)
            kc = jnp.reshape(cl["k"][tables],
                             (x_t.shape[0], -1, cfg.n_kv_heads, cfg.head_dim))
            vc = jnp.reshape(cl["v"][tables],
                             (x_t.shape[0], -1, cfg.n_kv_heads, cfg.head_dim))
            attn = decode_attention(q, kc, vc, cache_len, window=0,
                                    logit_cap=cfg.attn_softcap,
                                    scale=cfg.query_scale)
    y = _attn_out(attn, p["attn"]["wo"], approx_cfg, cfg)
    if cfg.post_norm:
        y = _apply_norm(p["post1"], y, cfg)
    x_t = res + y
    res = x_t
    h = _apply_norm(p["norm2"], x_t, cfg)
    y = _mlp_apply(p["mlp"], h, cfg, approx_cfg)
    if cfg.post_norm:
        y = _apply_norm(p["post2"], y, cfg)
    return res + y, cl


def paged_decode_step(params, cfg: ModelConfig, cache, token, *,
                      approx_cfg=0, backend="xla"):
    """One token for every row against the block pool.

    ``cache`` carries the pool leaves ("scan"/"rest{r}") plus three data
    operands: "tables" (B,P) int32 block tables, "seq_lens" (B,) int32,
    "active" (B,) bool.  Returns (logits (B,V), new pool leaves) — table
    bookkeeping stays on the host (serve/paged_cache.py)."""
    tables = cache["tables"]
    seq_lens = cache["seq_lens"]
    active = cache["active"]
    x = embed_tokens(params, cfg, token)
    if cfg.norm == "ln":
        x = x + jnp.take(params["dec_pos"], seq_lens, axis=0
                         )[:, None].astype(x.dtype)
    new_cache: Params = {}
    npat = len(cfg.pattern)
    _, acfg_scan, acfg_rest = _layer_cfg_plan(params["blocks"],
                                              approx_cfg, npat)

    if "scan" in params["blocks"]:
        def scan_fn(x, gp_cl_ac):
            gp, cl, ac = gp_cl_ac
            ncl = {}
            for j in range(npat):
                x, c = _paged_attn_block(
                    gp[f"b{j}"], x, cl[f"b{j}"], cfg, tables, seq_lens,
                    active,
                    approx_cfg=approx_cfg if ac is None else ac[j],
                    backend=backend)
                ncl[f"b{j}"] = c
            return x, ncl
        x, new_cache["scan"] = _scan_layer_groups(
            scan_fn, x, params["blocks"]["scan"], cache["scan"], acfg_scan,
            scan_layers=cfg.scan_layers)
    r = 0
    while f"rest{r}" in params["blocks"]:
        x, c = _paged_attn_block(
            params["blocks"][f"rest{r}"], x, cache[f"rest{r}"], cfg,
            tables, seq_lens, active,
            approx_cfg=approx_cfg if acfg_rest is None else acfg_rest[r],
            backend=backend)
        new_cache[f"rest{r}"] = c
        r += 1
    x = _apply_norm(params["final_norm"], x, cfg)
    logits = logits_for(params, cfg, x[:, 0])
    return logits, new_cache


def paged_prefill_chunk(params, cfg: ModelConfig, cache, tokens, *,
                        slot, start, count, approx_cfg=0):
    """Advance one request's prefill by one chunk of its prompt.

    tokens: (1, C) right-padded chunk; slot/start/count are traced int32
    scalars — the request's row, the absolute position of tokens[0], and
    the number of valid tokens in the chunk.  K/V for the valid tokens
    scatter into the slot's blocks (pads go to the trash block); each
    chunk position attends to every cached key at absolute position
    <= its own, so chaining chunks reproduces full-prompt prefill.
    Returns (logits (1,C,V) at EVERY chunk position, new pool leaves):
    prefill callers index ``count - 1`` on the host for the next-token
    sample; the speculative verify pass (DESIGN.md §12) consumes all
    rows — one chunk call scores k draft positions at once.
    """
    from repro.serve.paged_cache import TRASH_BLOCK

    from .attention import NEG_INF, _repeat_kv
    from .layers import apply_rope
    tables = cache["tables"]
    c_len = tokens.shape[1]
    tok_pos = start + jnp.arange(c_len)            # (C,) absolute
    positions = tok_pos[None]
    x = embed_tokens(params, cfg, tokens)
    if cfg.norm == "ln":
        x = x + jnp.take(params["dec_pos"], tok_pos, axis=0
                         )[None].astype(x.dtype)
    row = tables[slot]                             # (P,)
    scale = (cfg.query_scale if cfg.query_scale is not None
             else cfg.head_dim ** -0.5)

    def fill_chunk(p, x, cl, ac):
        h = _apply_norm(p["norm1"], x, cfg)
        q = _proj(h, p["attn"]["wq"], ac, p["attn"].get("bq"), cfg,
                  cfg.n_heads)
        k = _proj(h, p["attn"]["wk"], ac, p["attn"].get("bk"), cfg,
                  cfg.n_kv_heads)
        v = _proj(h, p["attn"]["wv"], ac, p["attn"].get("bv"), cfg,
                  cfg.n_kv_heads)
        if cfg.norm == "rms":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("attention"):
            bs = cl["k"].shape[1]
            blocks = jnp.where(jnp.arange(c_len) < count,
                               row[tok_pos // bs], TRASH_BLOCK)
            offs = tok_pos % bs
            cl = dict(cl)
            cl["k"] = cl["k"].at[blocks, offs].set(k[0].astype(cl["k"].dtype))
            cl["v"] = cl["v"].at[blocks, offs].set(v[0].astype(cl["v"].dtype))
            kc = jnp.reshape(cl["k"][row],
                             (1, -1, cfg.n_kv_heads, cfg.head_dim))
            vc = jnp.reshape(cl["v"][row],
                             (1, -1, cfg.n_kv_heads, cfg.head_dim))
            k_r = _repeat_kv(kc, cfg.n_heads // cfg.n_kv_heads)
            v_r = _repeat_kv(vc, cfg.n_heads // cfg.n_kv_heads)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                                k_r.astype(jnp.float32)) * scale
            if cfg.attn_softcap > 0:
                scores = softcap(scores, cfg.attn_softcap)
            key_pos = jnp.arange(kc.shape[1])
            valid = key_pos[None, :] <= tok_pos[:, None]       # (C, L)
            scores = jnp.where(valid[None, None], scores, NEG_INF)
            w = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", w,
                              v_r.astype(jnp.float32)).astype(q.dtype)
        y = _attn_out(attn, p["attn"]["wo"], ac, cfg)
        if cfg.post_norm:
            y = _apply_norm(p["post1"], y, cfg)
        x = x + y
        res = x
        h = _apply_norm(p["norm2"], x, cfg)
        y = _mlp_apply(p["mlp"], h, cfg, ac)
        if cfg.post_norm:
            y = _apply_norm(p["post2"], y, cfg)
        return res + y, cl

    new_cache: Params = {}
    npat = len(cfg.pattern)
    _, acfg_scan, acfg_rest = _layer_cfg_plan(params["blocks"],
                                              approx_cfg, npat)
    if "scan" in params["blocks"]:
        def scan_fn(x, gp_cl_ac):
            gp, cl, ac = gp_cl_ac
            ncl = {}
            for j in range(npat):
                x, c = fill_chunk(gp[f"b{j}"], x, cl[f"b{j}"],
                                  approx_cfg if ac is None else ac[j])
                ncl[f"b{j}"] = c
            return x, ncl
        x, new_cache["scan"] = _scan_layer_groups(
            scan_fn, x, params["blocks"]["scan"], cache["scan"], acfg_scan,
            scan_layers=cfg.scan_layers)
    r = 0
    while f"rest{r}" in params["blocks"]:
        x, c = fill_chunk(params["blocks"][f"rest{r}"], x,
                          cache[f"rest{r}"],
                          approx_cfg if acfg_rest is None else acfg_rest[r])
        new_cache[f"rest{r}"] = c
        r += 1
    x = _apply_norm(params["final_norm"], x, cfg)
    logits = logits_for(params, cfg, x)
    return logits, new_cache

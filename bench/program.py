"""The system under test, as the benchmark drives it: the program's model
config for a configuration file, the seeded weights in the layout the
program serves, and a paged ``Engine`` at the file's geometry.

This is the one module of the benchmark that imports the program."""
from __future__ import annotations

import dataclasses

import jax

from repro.configs.registry import get_config
from repro.core.quantization import QTensor
from repro.nn import transformer as T
from repro.serve.engine import Engine
from repro.serve.paged_cache import N_RESERVED, PagedCacheConfig

# configuration-file key (the published config.json's name) -> the
# program's ModelConfig field
MODEL_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "attention_bias": "qkv_bias",
    "num_experts": "n_experts", "num_experts_per_tok": "top_k",
    "norm_topk_prob": "renormalize",
}


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with the file's overrides, checked against every size the file
    states."""
    cfg = get_config(conf["registry"])
    over = dict(conf.get("overrides", {}))
    if conf.get("smoke"):
        cfg = cfg.smoke(**over)
    elif over:
        cfg = dataclasses.replace(cfg, **over)
    for key, value in conf["model"].items():
        got = getattr(cfg, MODEL_KEYS[key])
        if got != value:
            raise ValueError(f"{conf['name']}: the program runs {key}="
                             f"{got}, the configuration file states {value}")
    if len(cfg.pattern) != 1 or cfg.pattern[0] != "global":
        raise ValueError("the decoder_lm reference covers all-global "
                         "attention stacks only")
    return cfg


def serving_params(w: dict, cfg):
    """The plain weights in the program's serving layout (QTensor GEMM
    weights stacked under the scanned layer group)."""
    lw = w["layers"]

    def qt(name):
        return QTensor(lw[name], lw[name + "_s"], 1)

    attn = {n: qt(n) for n in ("wq", "wk", "wv", "wo")}
    for n in ("bq", "bk", "bv"):
        if n in lw:
            attn[n] = lw[n]
    mlp = {n: qt(n) for n in ("w_gate", "w_up", "w_down")}
    if "router" in lw:
        mlp["router"] = lw["router"]
    params = {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
              "blocks": {"scan": {"b0": {
                  "norm1": {"scale": lw["norm1"]}, "attn": attn,
                  "norm2": {"scale": lw["norm2"]}, "mlp": mlp}}}}
    if "lm_head" in w:
        params["lm_head"] = w["lm_head"]
    want = jax.eval_shape(lambda k: T.init_serving_lm(k, cfg)[0],
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda p: p, params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("seeded weights do not match the program's "
                         "serving layout")
    return params


def make_engine(params, cfg, geometry: dict, seed: int, clock):
    """A paged Engine at the configuration's geometry: a pool that holds
    max_batch full-length requests, the program's default backends."""
    bs = geometry["block_size"]
    num_blocks = geometry["max_batch"] * geometry["max_len"] // bs + N_RESERVED
    paged = PagedCacheConfig(num_blocks=num_blocks, block_size=bs,
                             prefill_chunk=geometry["prefill_chunk"])
    return Engine(params, cfg, max_batch=geometry["max_batch"],
                  max_len=geometry["max_len"], paged=paged,
                  seed=seed % (2 ** 31), clock=clock)


def new_request(spec):
    """The program's Request for a generated spec: greedy decoding."""
    from repro.serve.engine import Request
    return Request(rid=spec.rid, prompt=spec.prompt,
                   max_new_tokens=spec.max_new, temperature=0.0)


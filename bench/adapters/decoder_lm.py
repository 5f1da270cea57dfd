"""How the program serves a configuration of the ``decoder_lm`` reference
(``bench/refs/decoder_lm.py``): its configuration-file keys, the stacks
the reference covers, and the reference's weights in the program's
serving layout."""
from __future__ import annotations

from bench import program
from repro.core.quantization import QTensor

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
    """The program's ModelConfig for a configuration file, checked against
    every size the file states; the reference covers all-global attention
    stacks only."""
    cfg = program.model_config(conf, MODEL_KEYS)
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
    return program.checked_layout(params, cfg)

"""A copy of the benchmark with cells at the models' ``smoke()`` widths,
added the way a later change adds one: new configuration, traffic, cell
and metric files, new entries in ``BENCHMARK.json``, and the new cells
named in the ``workloads`` of the metrics they report.  One of them is
of an architecture the benchmark does not have: its reference, work
class and adapter are new files too.  No file that is there is edited."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

QWEN = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
        "vocab_size": 128, "rope_theta": 1000000.0,
        "tie_word_embeddings": True, "attention_bias": True}
OLMOE = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
         "vocab_size": 128, "rope_theta": 10000.0,
         "tie_word_embeddings": False, "num_experts": 64,
         "num_experts_per_tok": 8, "norm_topk_prob": False}
SERVING = {"max_batch": 4, "block_size": 16, "prefill_chunk": 32,
           "max_len": 128, "norm_eps": 1e-06}
CHAT = {"loop": "open", "rate_per_s": 4.0,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                       "min": 4, "max": 64},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                       "min": 4, "max": 32},
        "config_schedule": {"period_s": 1.0, "configs": [0, 31]},
        "drain_cap_s": 30, "sample": {"max_requests": 64, "min_tokens": 100000}}
DECODE = {"loop": "saturated", "queue_depth": 4,
          "prompt_len": {"dist": "uniform", "min": 8, "max": 32},
          "output_len": {"dist": "uniform", "min": 16, "max": 48},
          "config_schedule": {"period_s": 1.0, "configs": [0, 31]},
          "drain_cap_s": 30, "sample": {"max_requests": 64, "min_tokens": 100000}}
METRIC = '''"""Requests that finished, a count read from the window's records."""


def read(ctx):
    return float(sum(r.status == "done" for r in ctx.win.attempted()))
'''
WORK_METRIC = '''"""int8 operations of one token's layer GEMMs, by the cell's work class."""


def read(ctx):
    return ctx.shapes.gemm_ops()
'''

ATTN_METRIC = '''"""Roofline share of the decode calls' attention by the cell's own work
class (bench.readers)."""
from bench.readers import scope_roofline


def read(ctx):
    sh = ctx.shapes
    return scope_roofline(ctx, "_decode", "attention", sh.decode_attn_ops,
                          sh.decode_attn_bytes, ctx.peaks["bf16_flops"])
'''

# An architecture under a reference name no file of the repo has: the
# dense decoder, with its configuration file's keys named otherwise.
RENAMED = {"layers": 2, "width": 64, "heads": 2, "kv_heads": 2,
           "head_width": 32, "ffn_width": 128, "vocab": 128,
           "rope_base": 1000000.0, "tied": True, "biased_qkv": True}
RENAMED_REF = '''"""decoder_lm's arithmetic over a configuration file whose model keys
are named otherwise, with a work class of its own."""
from bench.refs import decoder_lm
from bench.work import Shapes

QMAX_INT8, QMAX_INT4 = decoder_lm.QMAX_INT8, decoder_lm.QMAX_INT4
NAMES = {"layers": "num_hidden_layers", "width": "hidden_size",
         "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
         "head_width": "head_dim", "ffn_width": "intermediate_size",
         "vocab": "vocab_size", "rope_base": "rope_theta",
         "tied": "tie_word_embeddings", "biased_qkv": "attention_bias"}


def plain(model):
    return {NAMES[k]: v for k, v in model.items()}


def make_weights(model, key):
    return decoder_lm.make_weights(plain(model), key)


def forward_logits(w, model, **kw):
    return decoder_lm.forward_logits(w, plain(model), **kw)


class Work(Shapes):
    @classmethod
    def of(cls, model):
        m = plain(model)
        return cls(m["num_hidden_layers"], m["hidden_size"],
                   m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"], m["intermediate_size"], m["vocab_size"])

    def decode_attn_bytes(self, rows, context):
        """Its own count: a cache held in float32, 4 bytes a value."""
        return 2.0 * Shapes.decode_attn_bytes(self, rows, context)


WORK = Work
'''
RENAMED_ADAPTER = '''"""The program's layout of the renamed reference: decoder_lm's, with
the configuration file's keys mapped by their own names."""
from bench import program
from bench.adapters import decoder_lm

MODEL_KEYS = {"layers": "n_layers", "width": "d_model", "heads": "n_heads",
              "kv_heads": "n_kv_heads", "head_width": "head_dim",
              "ffn_width": "d_ff", "vocab": "vocab_size",
              "rope_base": "rope_theta", "tied": "tie_embeddings",
              "biased_qkv": "qkv_bias"}


def model_config(conf):
    return program.model_config(conf, MODEL_KEYS)


serving_params = decoder_lm.serving_params
'''


# the smoke mixes check every finished request: a fault that hits some
# rows of the batch cannot hide from the sample


def build(dest: Path, limits: dict | None = None,
          configs: tuple = (0, 31)) -> Path:
    """Copy the benchmark to `dest` and add the smoke cells
    ``smoke-qwen.chat`` and ``smoke-olmoe.decode``, their files and a
    metric ``smoke.done_requests`` read in both, and the cell
    ``smoke-renamed.chat`` of the architecture ``renamed_lm`` (chat
    traffic, the dense decoder's smoke widths under other key names)
    with a metric ``smoke.gemm_ops`` read in it alone, and the per-layer
    metric ``attention_roofline.smoke-chat`` of its work class, which
    counts attention bytes its own way."""
    dest = Path(dest)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    (dest / "src").symlink_to(REPO / "src")
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    base = json.loads((REPO / "bench" / "configs" / "olmoe-1b-7b.json")
                      .read_text())
    for name, registry, reference, model, extra, over in (
            ("smoke-qwen", "qwen2.5-3b", "decoder_lm", QWEN, {}, {}),
            ("smoke-olmoe", "olmoe-1b-7b", "decoder_lm", OLMOE,
             {"moe_capacity_factor": 1.25}, {"n_experts": 64, "top_k": 8}),
            ("smoke-renamed", "qwen2.5-3b", "renamed_lm", RENAMED, {}, {})):
        conf = {"name": name, "registry": registry, "reference": reference,
                "smoke": True, "reduced": [], "source": base["source"],
                "model": model, "overrides": over,
                "serving": dict(SERVING, **extra)}
        (dest / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(conf))
    for name, mix in (("smoke-chat", CHAT), ("smoke-decode", DECODE)):
        mix = dict(mix, config_schedule={"period_s": 1.0,
                                         "configs": list(configs)})
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    (dest / "bench" / "metrics" / "smoke.done_requests.py").write_text(METRIC)
    (dest / "bench" / "metrics" / "smoke.gemm_ops.py").write_text(WORK_METRIC)
    (dest / "bench" / "metrics" / "attention_roofline.smoke-chat.py"
     ).write_text(ATTN_METRIC)
    (dest / "bench" / "refs" / "renamed_lm.py").write_text(RENAMED_REF)
    (dest / "bench" / "adapters" / "renamed_lm.py").write_text(
        RENAMED_ADAPTER)
    cells = {"smoke-qwen.chat": ("smoke-qwen", "smoke-chat"),
             "smoke-olmoe.decode": ("smoke-olmoe", "smoke-decode"),
             "smoke-renamed.chat": ("smoke-renamed", "smoke-chat")}
    for cell, (conf, mix) in cells.items():
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": mix, "chips": 1,
                                   "why": "smoke widths on the CPU"})
        (dest / "bench" / "cells" / f"{cell}.json").write_text(json.dumps(
            {"limits": limits or {f"gap_max_cfg{c}": 1e6 for c in configs}}))
    twins = {"qwen2.5-3b.chat": ["smoke-qwen.chat", "smoke-renamed.chat"],
             "olmoe-1b-7b.decode": ["smoke-olmoe.decode"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [t for w in m["workloads"]
                               for t in twins.get(w, [])]
    bench["end_to_end"] += [
        {"name": "smoke.done_requests", "unit": "1", "better": "higher",
         "bound": 0.25, "source": "host_clock"},
        {"name": "smoke.gemm_ops", "unit": "ops", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["smoke-renamed.chat"]}]
    bench["per_layer"].append(
        {"name": "attention_roofline.smoke-chat", "unit": "%",
         "better": "higher", "source": "device_trace",
         "layer": "paged attention", "moves": "itl_p95_ms",
         "workloads": ["smoke-renamed.chat"]})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest

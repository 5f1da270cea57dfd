"""A copy of the benchmark with cells at the models' ``smoke()`` widths,
added the way a later change adds one: new configuration, traffic, cell
and metric files, new entries in ``BENCHMARK.json``, and the new cells
named in the ``workloads`` of the metrics they report.  No file that is
there is edited."""
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


# the smoke mixes check every finished request: a fault that hits some
# rows of the batch cannot hide from the sample


def build(dest: Path, limits: dict | None = None,
          configs: tuple = (0, 31)) -> Path:
    """Copy the benchmark to `dest` and add the smoke cells
    ``smoke-qwen.chat`` and ``smoke-olmoe.decode``, their files and a
    metric ``smoke.done_requests`` read in both."""
    dest = Path(dest)
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    (dest / "src").symlink_to(REPO / "src")
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    base = json.loads((REPO / "bench" / "configs" / "olmoe-1b-7b.json")
                      .read_text())
    for name, model, extra, over in (
            ("smoke-qwen", QWEN, {}, {}),
            ("smoke-olmoe", OLMOE, {"moe_capacity_factor": 1.25},
             {"n_experts": 64, "top_k": 8})):
        conf = {"name": name, "registry": name.replace("smoke-", "")
                .replace("qwen", "qwen2.5-3b").replace("olmoe", "olmoe-1b-7b"),
                "reference": "decoder_lm", "smoke": True, "reduced": [],
                "source": base["source"], "model": model, "overrides": over,
                "serving": dict(SERVING, **extra)}
        (dest / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(conf))
    for name, mix in (("smoke-chat", CHAT), ("smoke-decode", DECODE)):
        mix = dict(mix, config_schedule={"period_s": 1.0,
                                         "configs": list(configs)})
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    (dest / "bench" / "metrics" / "smoke.done_requests.py").write_text(METRIC)
    cells = {"smoke-qwen.chat": ("smoke-qwen", "smoke-chat"),
             "smoke-olmoe.decode": ("smoke-olmoe", "smoke-decode")}
    for cell, (conf, mix) in cells.items():
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": mix, "chips": 1,
                                   "why": "smoke widths on the CPU"})
        (dest / "bench" / "cells" / f"{cell}.json").write_text(json.dumps(
            {"limits": limits or {f"gap_max_cfg{c}": 1e6 for c in configs}}))
    twin = {"qwen2.5-3b.chat": "smoke-qwen.chat",
            "olmoe-1b-7b.decode": "smoke-olmoe.decode"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [twin[w] for w in m["workloads"] if w in twin]
    bench["end_to_end"].append({"name": "smoke.done_requests", "unit": "1",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest

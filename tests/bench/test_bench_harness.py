"""The benchmark is driven by data, and refuses to run without its chip.

A configuration, a traffic mix, a cell and a metric added under new names
in a copy of the benchmark are found by name and run end to end (at the
models' smoke() widths, on the CPU, past the harness's look for a chip)
with no edit to any file that was there; so is an architecture, as its
reference, work class and adapter, and a per-layer metric that reads
the traced run's scopes through that work class."""
import argparse
import filecmp
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import _bench_smoke  # noqa: E402
from bench import readers, spans, trace  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.peaks import peaks  # noqa: E402

TICK = Path(__file__).resolve().parent / "data" / "chat_engine_tick.xplane.pb"


def _unchanged(copy: Path) -> bool:
    """Every file of the repo's benchmark is byte-identical in the copy."""
    for path in (REPO / "bench").rglob("*"):
        if path.is_file() and "__pycache__" not in path.parts:
            twin = copy / path.relative_to(REPO)
            if not filecmp.cmp(path, twin, shallow=False):
                return False
    return True


@pytest.mark.parametrize("cell", ["smoke-qwen.chat", "smoke-olmoe.decode",
                                  "smoke-renamed.chat"])
def test_cells_added_as_files_are_found_and_run(tmp_path, cell):
    root = _bench_smoke.build(tmp_path)
    assert _unchanged(root)
    args = argparse.Namespace(workload=cell, seed=2 ** 33 + 5, seconds=2.0,
                              trace=0)
    result = bench_run.run(args, root=root, require_tpu=False)
    metrics = result["metrics"]
    wanted = {"setup_s", "smoke.done_requests"} | (
        {"itl_p95_ms"} if cell.endswith("chat")
        else {"output_tok_s"}) | (
        {"smoke.gemm_ops"} if cell == "smoke-renamed.chat" else set())
    assert set(metrics) == wanted
    if "smoke.gemm_ops" in metrics:
        # the renamed architecture's own work class, read through its
        # own key names: 2 layers of q, k, v, o and a SwiGLU at width 64
        m = _bench_smoke.RENAMED
        per_layer = m["width"] * m["head_width"] * 2 * (
            m["heads"] + m["kv_heads"]) + 3 * m["width"] * m["ffn_width"]
        assert metrics["smoke.gemm_ops"]["value"] == \
            2.0 * m["layers"] * per_layer
    assert metrics["smoke.done_requests"]["value"] >= 1
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["notes"]["compiles_in_window"] == 0
    assert list(result)[-1] == "checks"
    assert result["correct"] is True


@pytest.mark.parametrize("cell", ["smoke-qwen.chat", "smoke-olmoe.decode",
                                  "smoke-renamed.chat"])
def test_a_traced_run_reads_every_per_layer_metric_of_its_cell(
        tmp_path, monkeypatch, cell):
    """``--trace 1`` drives the window under the profiler and reduces its
    trace with the scopes and ticks.  The CPU's profile has no device
    plane: the recorded TPU tick stands in for it.  Every per-layer
    metric of the cell is read, the added ``attention_roofline.smoke-chat``
    among them, but ``decode.moe_ms.decode``: the chat tick has no
    ``moe`` scope, so its reader finds nothing and is left out."""
    import jax
    root = _bench_smoke.build(tmp_path)
    monkeypatch.setattr(trace, "find_xplane", lambda log_dir: str(TICK))
    cached = jax.config.jax_enable_compilation_cache
    args = argparse.Namespace(workload=cell, seed=2 ** 33 + 7, seconds=2.0,
                              trace=1)
    try:
        result = bench_run.run(args, root=root, require_tpu=False)
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    assert _unchanged(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wanted = {m["name"] for m in bench_run.metrics_for(bench, cell, True)}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == wanted - {"decode.moe_ms.decode"}
    assert ("attention_roofline.smoke-chat" in metrics) == \
        (cell == "smoke-renamed.chat")
    kind = cell.rpartition(".")[2]
    assert metrics[f"engine.idle_ms_per_tick.{kind}"] == 14.453554
    if kind == "chat":
        assert metrics["decode.attention_ms.chat"] == 49.353812
    assert result["breakdown"]["idle_gaps"][0] == \
        ["engine.logits_to_host", 0.005482491]
    assert result["device"]["busy_s"] == 0.124829634
    assert result["notes"]["spans"]["engine_ticks"] == 1
    assert list(result)[-1] == "checks" and result["correct"] is True
    assert not (root / bench_run.TRACE_DIR).exists()


def test_a_work_class_counts_attention_its_own_way(tmp_path):
    """``attention_roofline.smoke-chat`` reads through the cell's work
    class: over the same window and trace, the renamed architecture's
    float32 cache count doubles the share the plain decoder's gives:
    the bytes bound a decode call's attention at these widths."""
    from types import SimpleNamespace as NS

    from jax.profiler import ProfileData
    root = _bench_smoke.build(tmp_path)
    raw = TICK.read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    reduced = trace.reduce(data)
    reduced.update(spans.reduce(data, spans.op_paths(raw)))
    win = NS(records=[NS(spec=NS(prompt=[0] * n), token_step=list(steps))
                      for n, steps in ((30, range(6)), (12, range(2, 9)))])
    got = {}
    for name in ("smoke-qwen", "smoke-renamed"):
        conf = json.loads((root / "bench" / "configs" / f"{name}.json")
                          .read_text())
        ref_mod, _ = bench_run.architecture(root, conf)
        ctx = readers.Context(win=win, conf=conf, mix={},
                              peaks=peaks("TPU v5 lite"), setup_s=1.0,
                              work=ref_mod.WORK, trace=reduced,
                              traced={"start_step": 1, "stop_step": 8})
        got[name] = bench_run.read_metric(
            root, "attention_roofline.smoke-chat", ctx)
    assert _unchanged(root)
    assert got["smoke-renamed"] == pytest.approx(2 * got["smoke-qwen"],
                                                 rel=1e-12)
    assert 0 < got["smoke-qwen"] < got["smoke-renamed"] < 100


def _add_config(root: Path, name: str, conf: dict) -> str:
    """A cell ``<name>.chat`` in the copy at `root` for the configuration
    `conf`, under `name`; returns the cell's name."""
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(dict(conf, name=name)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": f"{name}.chat", "config": name,
                               "traffic": "smoke-chat", "chips": 1,
                               "why": "refused before any run"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "cells" / f"{name}.chat.json").write_text(
        json.dumps({"limits": {"gap_max_cfg0": 1e6}}))
    return f"{name}.chat"


@pytest.mark.parametrize("case", ["missing_adapter", "unmapped_key"])
def test_an_architecture_it_cannot_run_is_refused_by_name(tmp_path, case):
    root = _bench_smoke.build(tmp_path)
    conf = json.loads((root / "bench" / "configs" / "smoke-qwen.json")
                      .read_text())
    if case == "missing_adapter":
        shutil.copy(root / "bench" / "refs" / "renamed_lm.py",
                    root / "bench" / "refs" / "lonely_lm.py")
        conf = dict(conf, reference="lonely_lm",
                    model=_bench_smoke.RENAMED)
        said = "no bench/adapters/lonely_lm.py"
    else:
        conf = dict(conf, model=dict(conf["model"], kv_lora_rank=512))
        said = "bench/adapters/decoder_lm.py maps no model key 'kv_lora_rank'"
    cell = _add_config(root, f"smoke-{case}", conf)
    args = argparse.Namespace(workload=cell, seed=7, seconds=1.0, trace=0)
    with pytest.raises(bench_run.Refused, match=re.escape(said)):
        bench_run.run(args, root=root, require_tpu=False)


def test_only_program_and_adapters_import_the_program():
    """The benchmark takes the system under test through bench/program.py
    and bench/adapters/ alone; a reference or a work class never does."""
    importing = {path.relative_to(REPO).as_posix()
                 for path in (REPO / "bench").rglob("*.py")
                 if re.search(r"^\s*(from|import)\s+repro\b",
                              path.read_text(), re.M)}
    assert {"bench/program.py", "bench/adapters/decoder_lm.py"} <= importing
    assert all(path == "bench/program.py"
               or path.startswith("bench/adapters/") for path in importing)


def test_same_seed_sends_the_same_requests_and_seeds_share_sizes():
    from bench import generator
    mix = json.loads((REPO / "bench" / "traffic" / "chat.json").read_text())
    a = generator.open_loop(mix, 7, 30.0, 1000)
    b = generator.open_loop(mix, 7, 30.0, 1000)
    c = generator.open_loop(mix, 2 ** 40 + 3, 30.0, 1000)
    assert [(s.due_s, s.max_new, s.prompt.tolist()) for s in a] == \
        [(s.due_s, s.max_new, s.prompt.tolist()) for s in b]
    assert sorted(len(s.prompt) for s in a) == \
        sorted(len(s.prompt) for s in c)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in c)
    assert [s.max_new for s in a] != [s.max_new for s in c]
    assert all(0 <= s.due_s < 30.0 for s in a + c)


def _run_py(cwd: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    r = _run_py(REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""

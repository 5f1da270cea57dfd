#!/usr/bin/env python3
"""Chip benchmark of the served path: one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and the limits of its correctness
check (``bench/cells/<workload>.json``).  Each metric is read by
``bench/metrics/<metric>.py``.  A configuration names its architecture's
plain reference, ``bench/refs/<reference>.py`` (its seeded weights, its
forward pass and its work class ``WORK``), and the program's layout of
it, ``bench/adapters/<reference>.py`` (the configuration-file keys it
maps, ``model_config`` and ``serving_params``).  Everything is found by
name and loaded by path under the run's root, so a cell, a mix, a
metric or an architecture is added by adding files.

The run makes the served weights on the device from the seed, builds a
paged ``Engine`` at the configuration's geometry, warms up every shape
the cell's traffic uses (set-up), then drives ``Engine.submit`` /
``Engine.step`` for ``--seconds`` with the mix's requests and follows
the requests that arrived to completion.  With ``--trace 0`` it reports
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
from a profiler trace of part of the window, reduced by
``bench/trace.py`` and ``bench/spans.py`` (device time per executable,
per GEMM and per model scope, idle time per engine span).  A traced run
compiles without the persistent compilation cache: JAX keys the cache
on the module without its op names, so a cached executable would bring
another build's names, and no scope, into the trace.  After the window it
checks the served tokens against the plain reference (bench/correct.py)
and prints every number compared beside its limit, on standard error
and as the last key of the result: the last line of standard output,
one JSON object.

It needs the accelerator the cell names: without it, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_DIR = ".bench_trace"          # under the run's root
# the trace covers the window's last seconds: stopping the profiler holds
# the host for seconds, and at the window's close no arrival is left to
# send late
TRACE_SECONDS = 8.0
WARMUP_NEW_TOKENS = 4


class Refused(RuntimeError):
    """The run cannot be made here; no result is printed."""


class CompileLog:
    """Requests for a compiled executable, counted from JAX's monitoring
    events: ``count`` every request (those the persistent cache served
    too), ``hits`` those the persistent cache served."""

    def __init__(self):
        self.count = self.hits = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += float(duration)

    def hit(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1


def load_cell(root: Path, workload: str) -> tuple[dict, dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix, limits) by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = json.loads((root / "bench" / "configs"
                       / f"{cell['config']}.json").read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "cells"
                         / f"{workload}.json").read_text())
    return bench, cell, conf, mix, limits


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_module(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under the run's root, loaded by path."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {path.relative_to(root)} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(root: Path, name: str, ctx):
    return load_module(root, "metrics", name).read(ctx)


def architecture(root: Path, conf: dict):
    """(reference, adapter) modules of a configuration's ``reference``;
    Refused where the adapter is missing or maps not every ``model`` key
    of the file."""
    ref = conf["reference"]
    ref_mod = load_module(root, "refs", ref)
    adapter = load_module(root, "adapters", ref)
    unmapped = sorted(set(conf["model"]) - set(adapter.MODEL_KEYS))
    if unmapped:
        raise Refused(f"bench/adapters/{ref}.py maps no model key "
                      f"{', '.join(map(repr, unmapped))} of configuration "
                      f"{conf['name']!r}")
    return ref_mod, adapter


def check_devices(cell: dict):
    """The device list, or Refused where the cell's chips are missing."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < cell["chips"]:
        raise Refused(f"the cell needs {cell['chips']} chips, JAX found "
                      f"{len(devs)}")
    return devs


def warm_up(eng, mix: dict, conf: dict, seed: int, vocab: int) -> None:
    """Run every shape the cell's traffic uses once: the one-chunk prefill
    (a full chunk, so every block of a chunk is written), the mid-prompt
    chunk (a prompt over one chunk), where the mix sends such prompts, and
    decode at max_batch.  The error config is a traced operand, so one
    config warms every other.  Prompts come from their own seed stream."""
    import numpy as np

    from bench import generator, program
    rng = generator.rng_for(seed, "warmup")
    chunk = conf["serving"]["prefill_chunk"]
    longest = min(mix["prompt_len"]["max"], conf["serving"]["max_len"] - 1
                  - WARMUP_NEW_TOKENS)
    lengths = sorted({min(chunk, longest), min(chunk + 1, longest)})
    eng.set_approx_cfg(generator.configs(mix)[0])
    for i, n in enumerate(lengths):
        spec = generator.Spec(-1 - i, 0.0, rng.integers(0, vocab, n,
                                                        dtype=np.int32),
                              WARMUP_NEW_TOKENS)
        eng.submit(program.new_request(spec))
    eng.run()


def reduce_trace(log_dir: Path, n_devices: int) -> dict:
    """``bench/trace.py``'s and ``bench/spans.py``'s reductions of the
    profile under `log_dir`, in one dict."""
    from jax.profiler import ProfileData

    from bench import spans, trace
    raw = Path(trace.find_xplane(str(log_dir))).read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    out = trace.reduce(data, n_devices=n_devices)
    out.update(spans.reduce(data, spans.op_paths(raw), n_devices=n_devices))
    return out


class Cell:
    """A cell ready to measure: its files, the device, the seeded weights
    and a warmed-up engine."""

    def __init__(self, args, root: Path = ROOT, require_tpu: bool = True,
                 patch_engine=None):
        import jax

        from bench import program
        from bench.peaks import peaks

        self.args, self.root = args, root
        (self.bench, self.cell, self.conf, self.mix,
         self.limits) = load_cell(root, args.workload)
        self.devs = check_devices(self.cell) if require_tpu \
            else jax.devices()
        self.peaks = peaks(self.devs[0].device_kind) if require_tpu else \
            {"bf16_flops": 1.0, "int8_ops": 1.0, "hbm_bytes_per_s": 1.0}
        if args.trace:
            jax.config.update("jax_enable_compilation_cache", False)
        elif require_tpu:
            program.enable_compile_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.log = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(self.log)
        jax.monitoring.register_event_listener(self.log.hit)
        self.ref_mod, adapter = architecture(root, self.conf)
        self.work = self.ref_mod.WORK
        self.cfg = adapter.model_config(self.conf)
        self.vocab = self.cfg.vocab_size
        model = self.conf["model"]
        key = jax.random.fold_in(jax.random.PRNGKey(args.seed % (2 ** 31)),
                                 args.seed // (2 ** 31))
        self.memory = {}             # device bytes in use after each stage
        self.weights = jax.jit(
            lambda k: self.ref_mod.make_weights(model, k))(key)
        self._note_memory("weights")
        params = adapter.serving_params(self.weights, self.cfg)
        self.eng = program.make_engine(params, self.cfg, self.conf["serving"],
                                       args.seed, clock=time.perf_counter)
        self._note_memory("engine")
        if patch_engine is not None:
            patch_engine(self.eng)
        warm_up(self.eng, self.mix, self.conf, args.seed, self.vocab)
        jax.block_until_ready(self.eng.cache)
        self._note_memory("warm_up")
        self.setup_compiles = (self.log.count, self.log.hits)

    def _note_memory(self, stage: str) -> None:
        stats = self.devs[0].memory_stats() or {}
        if "bytes_in_use" in stats:
            self.memory[stage] = (stats["bytes_in_use"],
                                  stats.get("peak_bytes_in_use"))

    def measure(self):
        """The window; returns (window, peak bytes, trace, traced counters,
        compiles inside the window).  Frees the engine afterwards."""
        import gc

        import jax

        from bench import loop
        args, eng = self.args, self.eng
        tracer, counters = None, {}
        trace_dir = self.root / TRACE_DIR
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = loop.Tracer(str(trace_dir),
                                 max(0.0, args.seconds - TRACE_SECONDS),
                                 args.seconds)
            tracer.snapshot = lambda tag: counters.update({
                f"prefill_tokens_{tag}": eng.n_prefill_tokens})
        annotate = (lambda name: jax.profiler.TraceAnnotation(name)) \
            if args.trace else None
        before = self.log.count
        win = loop.run(eng, self.mix, args.seconds, args.seed, self.vocab,
                       clock=time.perf_counter, annotate=annotate,
                       tracer=tracer)
        compiles = self.log.count - before
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devs[:self.cell["chips"]])
        trace = None
        if tracer is not None:
            counters.update(start_step=tracer.started_step,
                            stop_step=tracer.stopped_step)
            trace = reduce_trace(trace_dir, self.cell["chips"])
            shutil.rmtree(trace_dir, ignore_errors=True)
        self.eng = None
        del eng
        gc.collect()
        return win, peak, trace, counters, compiles

    def reference(self, qmax: int | None = None):
        from bench import correct, generator
        return correct.Reference(
            self.ref_mod, self.conf, self.weights,
            generator.configs(self.mix), self.mix["output_len"]["max"],
            self.ref_mod.QMAX_INT8 if qmax is None else qmax)


def run(args, root: Path = ROOT, require_tpu: bool = True,
        patch_engine=None) -> dict:
    """One run of one cell; returns the result object."""
    from bench import correct, generator, readers, spans

    cell = Cell(args, root, require_tpu, patch_engine)
    setup_s = time.perf_counter() - T_START
    win, peak, trace, counters, compiles = cell.measure()

    ctx = readers.Context(win=win, conf=cell.conf, mix=cell.mix,
                          peaks=cell.peaks, setup_s=setup_s, work=cell.work,
                          trace=trace, traced=counters)
    metrics = {}
    for m in metrics_for(cell.bench, args.workload, bool(args.trace)):
        v = read_metric(root, m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    cfgs = generator.configs(cell.mix)
    chosen = correct.sample(win, cell.mix, args.seed)
    cmp = correct.compare(chosen, win.steps, cell.reference())
    found = correct.numbers(cmp, cfgs)
    checks = {name: {"value": found.get(name), "limit": limit}
              for name, limit in cell.limits["limits"].items()}
    ok = bool(chosen) and cmp["skipped"] == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    dev = cell.devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devs), "memory_peak_bytes": int(peak)}
    result = {"correct": ok, "attempted": len(win.attempted()),
              "failed": len(win.failed()), "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["top_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    ticks = win.steps[:win.close_step]
    tick_s = sum(t.end_s - t.start_s for t in ticks)
    result["notes"] = {"compiles_in_setup": cell.setup_compiles[0],
                       "cache_hits_in_setup": cell.setup_compiles[1],
                       "compiles_in_window": compiles,
                       "window_ticks": len(ticks),
                       "tick_ms_mean": 1e3 * tick_s / max(len(ticks), 1),
                       "between_ticks_ms_mean": 1e3 * (
                           (ticks[-1].end_s - ticks[0].start_s - tick_s)
                           / len(ticks)) if ticks else None,
                       "sampled_requests": len(chosen),
                       "sampled_tokens": int(cmp["gap"].size),
                       "drain_capped": win.drain_capped}
    if trace is not None:
        result["notes"]["spans"] = spans.readings(trace)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip at the
cell's own size: for each seed, a run of the cell's window and, over the
same sample of served positions, the gap of

* the served tokens (the program, the lower reading);
* the control's first choices: the plain reference in the nearest lower
  precision than the configuration's int8 (int4), put in the program's
  place (the upper reading);
* the fault "a token altered where it is produced", read over the same
  positions: every served token replaced by another drawn from the
  seed (``altered_all``), and one row in ``max_batch`` so replaced
  (``altered_row``), as a decode step that alters one row per call.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 20 \\
        --out control.json

Every seed runs in this one process; the per-position readings go to
``--out`` and a summary per seed and config to standard output.
``--configs 0,31`` runs the window under another live schedule than the
mix's, to read a config the cell does not run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--configs", default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import numpy as np

    from bench import correct, generator, readers
    out = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        run_args = argparse.Namespace(workload=args.workload, seed=seed,
                                      seconds=args.seconds, trace=0)
        cell = bench_run.Cell(run_args)
        if args.configs:
            cell.mix = dict(cell.mix, config_schedule=dict(
                cell.mix["config_schedule"],
                configs=[int(c) for c in args.configs.split(",")]))
        setup = time.perf_counter() - t0
        win, peak, _, _, compiles = cell.measure()
        chosen = correct.sample(win, cell.mix, seed)
        ref = cell.reference()
        control = cell.reference(qmax=cell.ref_mod.QMAX_INT4)
        vocab = cell.vocab
        rng = generator.rng_for(seed, "sample")
        alt_all, alt_row = [], []

        def altered(rec, lg, at):
            served = np.asarray(rec.req.tokens, np.int32)
            other = (served + 1 + rng.integers(vocab - 1, size=served.size)) \
                % vocab
            g_alt = correct.gaps(lg, other)
            hit = rng.random(served.size) < 1.0 / cell.conf["serving"][
                "max_batch"]
            alt_all.append(g_alt)
            alt_row.append(np.where(hit, g_alt, correct.gaps(lg, served)))

        t1 = time.perf_counter()
        cmp = correct.compare(chosen, win.steps, ref, control, altered)
        ref_s = time.perf_counter() - t1
        cmp["altered_all"] = np.concatenate(alt_all) if alt_all else \
            np.zeros(0)
        cmp["altered_row"] = np.concatenate(alt_row) if alt_row else \
            np.zeros(0)
        ctx = readers.Context(win=win, conf=cell.conf, mix=cell.mix,
                              peaks=cell.peaks, setup_s=setup,
                              work=cell.work)
        load = {name: bench_run.read_metric(ROOT, name, ctx)
                for name in ("output_tok_s", "itl_p95_ms")}
        load["ttft_p95_ms"] = 1e3 * readers.p95(readers.ttfts(ctx))
        load["drain_s"] = win.end_s - win.seconds
        cfgs = generator.configs(cell.mix)
        summary = {"setup_s": setup, "peak_bytes": int(peak), **load,
                   "compiles_in_window": compiles,
                   "attempted": len(win.attempted()),
                   "failed": len(win.failed()),
                   "sampled": len(chosen), "positions": int(cmp["gap"].size),
                   "skipped": cmp["skipped"], "reference_s": ref_s,
                   **{key: correct.numbers(cmp, cfgs, key)
                      for key in ("gap", "control_gap", "altered_all",
                                  "altered_row")}}
        print(json.dumps({"seed": seed, **summary}), flush=True)
        out[seed] = {"summary": summary,
                     **{k: np.asarray(v).tolist() for k, v in cmp.items()
                        if k != "skipped"}}
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out))
        del cell, ref, control
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

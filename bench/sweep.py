#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, by a sweep on
the chip: one engine, one window per rate (lowest first), each drained
before the next.  Per rate it prints the requests sent, the p95 time to
first token, the p95 queue wait, the requests still waiting when the
window closed and how long the drain took.  The rate of the cell's mix
is set from this once, when the cell is defined (at about four fifths
of the highest rate sustained), not by the benchmark's runs.  Give each
rate a window as long as the cell's runs: a pool that fills only after
some tens of seconds looks unloaded in a shorter one.

    python3 bench/sweep.py --workload qwen2.5-3b.chat --seed 1 \\
        --seconds 51 --rates 0.6,0.75,0.9,1.05
"""
from __future__ import annotations

import argparse
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import loop, readers
    cell = bench_run.Cell(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=0))
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        win = loop.run(cell.eng, mix, args.seconds, args.seed + i,
                       cell.vocab, clock=time.perf_counter)
        ctx = readers.Context(win=win, conf=cell.conf, mix=mix,
                              peaks=cell.peaks, setup_s=0.0, work=cell.work)
        waits = [r.admit_s - r.due_s for r in win.attempted()
                 if r.admit_s is not None]
        waiting = sum(1 for r in win.attempted()
                      if r.admit_s is None or r.admit_s > win.seconds)
        print(json.dumps({
            "rate_per_s": rate, "sent": len(win.attempted()),
            "failed": len(win.failed()),
            "ttft_p95_ms": 1e3 * readers.p95(readers.ttfts(ctx)),
            "itl_p95_ms": 1e3 * (readers.p95(readers.token_gaps(ctx)) or 0),
            "queue_wait_p95_ms": 1e3 * (readers.p95(waits) or 0),
            "waiting_at_close": waiting,
            "drain_s": win.end_s - win.seconds,
            "output_tok_s": sum(sum(1 for t in r.token_s if t < win.seconds)
                                for r in win.records) / win.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

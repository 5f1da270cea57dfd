"""Least compute time of the window's useful work (int8 GEMM operations
at the int8 peak, attention and LM head at the bf16 peak) over the host
time of the engine ticks that ran it."""
from bench.readers import least_compute_s, window_steps


def read(ctx):
    steps = window_steps(ctx)
    busy = sum(s.end_s - s.start_s for s in ctx.win.steps[steps.start:
                                                          steps.stop])
    return 100.0 * least_compute_s(ctx, steps) / busy if busy else None

"""Least compute time of the window's useful work (int8 GEMM operations
at the int8 peak, attention and LM head at the bf16 peak) over the
window's seconds."""
from bench.readers import least_compute_s, window_steps


def read(ctx):
    return 100.0 * least_compute_s(ctx, window_steps(ctx)) / ctx.win.seconds

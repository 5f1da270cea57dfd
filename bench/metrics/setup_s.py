"""Seconds from process start to the window's first arrival: weights,
engine, warm-up and, in a run that compiles, compilation."""


def read(ctx):
    return ctx.setup_s

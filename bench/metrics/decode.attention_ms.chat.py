"""Device time per call of the decode executable inside the model's
scope ``attention`` (KV write, gather, softmax, weighted sum) in the
traced window (bench.spans).  Operations XLA names after the layer
loop, the K/V converts among them, carry no scope and are left out."""
from bench.readers import scope_ms_per_call


def read(ctx):
    return scope_ms_per_call(ctx.trace, "_decode", "attention")

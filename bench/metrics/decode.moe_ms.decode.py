"""Device time per call of the decode executable inside the model's
scope ``moe`` (routing, dispatch, expert FFN, combine) in the traced
window (bench.spans).  Operations XLA names after the layer loop, the
combine scatter among them, carry no scope and are left out."""
from bench.readers import scope_ms_per_call


def read(ctx):
    return scope_ms_per_call(ctx.trace, "_decode", "moe")

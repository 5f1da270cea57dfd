"""Device time per call of the decode executable in the traced window."""
from bench.readers import module_s


def read(ctx):
    m = module_s(ctx, "_decode")
    return None if m is None else 1e3 * m[1] / m[0]

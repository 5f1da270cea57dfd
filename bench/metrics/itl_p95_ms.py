"""p95 over every gap between consecutive output tokens of the requests
that arrived in the window, as the host saw them, in ms."""
from bench.readers import p95, token_gaps


def read(ctx):
    v = p95(token_gaps(ctx))
    return None if v is None else 1e3 * v

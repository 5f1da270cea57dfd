"""Device idle time inside the traced window's whole ``engine.tick``
spans, per tick: the time the engine's host work holds the chip
(bench.spans)."""
from bench.readers import idle_ms_per_tick


def read(ctx):
    return idle_ms_per_tick(ctx.trace)

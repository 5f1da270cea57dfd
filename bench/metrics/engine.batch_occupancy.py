"""Mean occupied decode slots over max_batch, over the window's ticks."""


def read(ctx):
    steps = ctx.win.steps[:ctx.win.close_step]
    if not steps:
        return None
    return 100.0 * sum(s.active for s in steps) / len(steps) / ctx.max_batch

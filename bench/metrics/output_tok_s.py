"""Output tokens the host saw during the window, over its seconds."""


def read(ctx):
    win = ctx.win
    n = sum(sum(1 for t in r.token_s if t < win.seconds)
            for r in win.records)
    return n / win.seconds

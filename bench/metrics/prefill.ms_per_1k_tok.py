"""Device time of the prefill executables (one-chunk and mid-prompt
chunk) per 1,000 prompt tokens advanced during the traced window."""
from bench.readers import module_s


def read(ctx):
    t = ctx.traced
    tokens = t.get("prefill_tokens_stop", 0) - t.get("prefill_tokens_start", 0)
    secs = sum(m[1] for m in (module_s(ctx, "_prefill"),
                              module_s(ctx, "_prefill_chunk")) if m)
    if tokens <= 0 or not secs:
        return None
    return 1e3 * secs / (tokens / 1e3)

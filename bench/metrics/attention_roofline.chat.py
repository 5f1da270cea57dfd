"""Roofline share of the decode calls' attention: per traced decode
call, the least time of its work by the cell's work class (its
``decode_attn_ops`` at the bf16 peak, or its ``decode_attn_bytes`` at
819 GB/s, whichever is longer), summed, over the device time of the
decode executable inside scope ``attention`` (bench.readers).  The
share overstates: operations XLA names after the layer loop, the K/V
converts among them, carry no scope and lie outside that time."""
from bench.readers import scope_roofline


def read(ctx):
    sh = ctx.shapes
    return scope_roofline(ctx, "_decode", "attention", sh.decode_attn_ops,
                          sh.decode_attn_bytes, ctx.peaks["bf16_flops"])

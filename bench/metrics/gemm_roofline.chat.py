"""Roofline share of the decode executable's matrix-unit work: the least
time its useful operations and bytes need at the chip's peaks, over the
device time of its matrix-unit operations (bench.readers)."""
from bench.readers import decode_roofline


def read(ctx):
    return decode_roofline(ctx)

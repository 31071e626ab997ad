"""Least time of the window's paged decode-attention calls over their
device time in the trace, in %."""
import reduce


def read(ctx):
    return reduce.roofline(ctx, "attn")

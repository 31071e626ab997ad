"""Useful model operations of the window (2 x matmul weights x real tokens,
plus attention over the lengths actually attended; pad rows left out) over
device-busy time x the bf16 peak, in %."""
import reduce


def read(ctx):
    return reduce.mfu(ctx)

"""Least time of the window's grouped ternary expert GEMM calls on the chip
(larger of operations over peak and bytes over bandwidth, from the rows
and active experts the engine counted) over their device time in the
trace, in %."""
import reduce


def read(ctx):
    return reduce.roofline(ctx, "expert")

"""Least time of the window's ternary GEMM and fused-MLP calls on the chip
(larger of operations over peak and bytes over bandwidth) over their
device time in the trace, in %."""
import reduce


def read(ctx):
    return reduce.roofline(ctx, "gemm")

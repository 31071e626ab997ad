"""Mean share of the engine's slots holding a request (decoding or
prefilling), over the window's steps, in %."""
import reduce


def read(ctx):
    return reduce.mean_share(ctx["occupancy"])

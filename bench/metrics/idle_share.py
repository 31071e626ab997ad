"""Share of the traced window in which no operation ran on the device,
in %."""
import reduce


def read(ctx):
    return reduce.idle_share(ctx)

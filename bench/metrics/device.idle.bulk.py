"""Share of the traced window in which no operation ran on the device, in
%, mean over chips."""

import layers


def read(ctx):
    return layers.idle_pct(ctx)

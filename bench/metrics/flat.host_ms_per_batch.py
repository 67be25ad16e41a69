"""flat.host_ms_per_batch: engine-step wall minus the device-busy time
inside it, mean per dispatched batch, in ms (flat route)."""

import layers


def read(ctx):
    if ctx.cfg["route"] != "flat":
        return None
    return layers.host_ms_per_step(ctx)

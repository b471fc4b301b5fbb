"""Lanes the fused kernel flagged to the bit-exact recompute, per
million lanes the window's sweeps dispatched: the program's
``kernel_flagged_lanes`` over ``sweep_lanes`` (``crush/mapper.PERF``,
the driver's deltas over the window). A flagged lane is one whose two
best draws landed inside the kernel's margin, or whose third could
overtake; each is recomputed on the XLA general path, up to a 256th of
a block at a time."""


def read(ctx, variant=None):
    lanes = ctx.obs.get("sweep_lanes")
    if "kernel_flagged_lanes" not in ctx.obs or not lanes:
        return None                      # a program from before the counter
    return 1e6 * ctx.obs["kernel_flagged_lanes"] / lanes

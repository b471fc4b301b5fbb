"""Share of an indep block's lane-rounds that had anything to place:
the program's ``indep_lane_rounds_needed`` (lanes that still had a
position to fill, summed over the rounds) over ``indep_rounds`` x the
lanes of a block (``sweep_lanes`` / ``sweep_blocks``), the driver's
deltas of ``crush/mapper.PERF`` over the window. 100 would be a loop
that runs no lane that has nothing left to fill."""


def read(ctx, variant=None):
    rounds = ctx.obs.get("indep_rounds")
    lanes, blocks = ctx.obs.get("sweep_lanes"), ctx.obs.get("sweep_blocks")
    if not rounds or not lanes or not blocks:
        return None
    return 100.0 * ctx.obs.get("indep_lane_rounds_needed", 0) \
        / (rounds * lanes / blocks)

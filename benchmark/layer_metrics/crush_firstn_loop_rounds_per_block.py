"""Rounds of the firstn retry loop a sweep block ran, summed over its
slots: the program's ``firstn_loop_rounds`` over ``sweep_blocks``
(``crush/mapper.PERF``, the driver's deltas over the window). A round
is a descent of every lane of the block, and a slot's loop goes round
while any of its lanes has a try left to make: its unluckiest lane's
tries less the two speculative ones."""


def read(ctx, variant=None):
    blocks = ctx.obs.get("sweep_blocks")
    if "firstn_loop_rounds" not in ctx.obs or not blocks:
        return None                      # a program from before the counter
    return ctx.obs["firstn_loop_rounds"] / blocks

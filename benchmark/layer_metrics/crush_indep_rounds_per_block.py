"""Rounds an indep block of the window's sweeps ran: the program's
``indep_rounds`` over ``indep_blocks`` (``crush/mapper.PERF``, the
driver's deltas over the window). A round is every position's descent
at the block's full width, and a block goes round again while any of
its lanes has a position unfilled."""


def read(ctx, variant=None):
    blocks = ctx.obs.get("indep_blocks")
    if not blocks:                       # a program from before the counter
        return None
    return ctx.obs.get("indep_rounds", 0) / blocks

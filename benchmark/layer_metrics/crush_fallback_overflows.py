"""Blocks of the window's sweeps whose flagged lanes exceeded the
fallback buffer and were recomputed at full width on the XLA general
path: the program's ``kernel_fallback_overflows`` (``crush/mapper.PERF``,
the driver's delta over the window). 0 on a sound run: one such block
costs more than a hundred ordinary ones."""


def read(ctx, variant=None):
    if "kernel_fallback_overflows" not in ctx.obs:
        return None                      # a program from before the counter
    return ctx.obs["kernel_fallback_overflows"]

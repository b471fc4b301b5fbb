"""1 - union of the device's operation intervals over the traced
stretch (the busiest device where there are several)."""


def read(ctx, variant=None):
    if ctx.trace is None or ctx.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s("max") * 1e9 / ctx.trace.window_ns)

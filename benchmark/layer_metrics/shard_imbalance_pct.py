"""(busiest - idlest device's busy time) / busiest over the traced
stretch. Needs more than one device in the trace."""


def read(ctx, variant=None):
    if ctx.trace is None or len(ctx.trace.busy_ns) < 2:
        return None
    hi, lo = ctx.trace.busy_s("max"), ctx.trace.busy_s("min")
    if hi <= 0:
        return None
    return 100.0 * (hi - lo) / hi

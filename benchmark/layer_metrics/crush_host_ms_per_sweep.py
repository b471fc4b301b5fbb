"""Wall of a traced sweep less the time the device was busy inside it:
dispatch, staging and the read-back of the counts. Mean over the sweeps
that lie wholly in the traced stretch (``bench.sweep`` spans; on four
chips the busiest device). The host's and the device's clocks agree to
about a millisecond in a trace, so this is no finer than that."""


def read(ctx, variant=None):
    if ctx.trace is None:
        return None
    spans = ctx.trace.spans_named("bench.sweep")
    if not spans:
        return None
    wall = sum(b - a for a, b in spans) / 1e9
    busy = ctx.trace.busy_within(spans)
    return 1e3 * (wall - busy) / len(spans)

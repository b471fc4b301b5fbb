"""Device busy time of the traced sweeps over the mappings a device
did in them (on four chips: the busiest device and its quarter)."""


def read(ctx, variant=None):
    if ctx.trace is None:
        return None
    spans = ctx.trace.spans_named("bench.sweep")
    if not spans:
        return None
    per_device = len(spans) * ctx.obs["inputs_per_sweep"] \
        / len(ctx.trace.busy_ns)
    return 1e9 * ctx.trace.busy_within(spans) / per_device

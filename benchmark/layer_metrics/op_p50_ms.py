"""Median of the client's per-op latency over all ops of the window."""

from harness.stats import percentile


def read(ctx, variant=None):
    lat = ctx.obs.get("op_lat_s")
    if not lat:
        return None
    return percentile(lat, 50) * 1e3

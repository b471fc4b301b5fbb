"""Median wall of one sweep call, the driver's clock around it."""

from harness.stats import percentile


def read(ctx, variant=None):
    walls = ctx.obs.get("sweep_s")
    if not walls:
        return None
    return percentile(walls, 50) * 1e3

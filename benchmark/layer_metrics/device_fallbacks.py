"""Answers that came from a host fallback inside the window: the
health counters of ``chip_smoke.py`` (aggregators, devmon, mapper) and,
for a sweep, one whose ``last_map_path`` is not the path
``mapping_path()`` promised."""

from harness import counters


def read(ctx, variant=None):
    if not ctx.delta:
        return None
    return counters.fallbacks(ctx.delta) + ctx.obs.get("sweeps_off_path", 0)

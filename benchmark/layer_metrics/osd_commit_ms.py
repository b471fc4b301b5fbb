"""Mean time from an EC write's fan-out to the last of its k+m commits:
the OSDs' ``commit_latency`` (``sum`` over ``avgcount``, added up over
``ctx.osds``) since they started. ``harness/counters.snapshot`` has no
key for it, so there is no window delta; the warm-up before the window
is the same writes."""


def read(ctx, variant=None):
    n = s = 0.0
    for o in ctx.osds:
        lat = o.perf.dump().get("commit_latency") or {}
        n += lat.get("avgcount", 0)
        s += lat.get("sum", 0.0)
    if not n:
        return None
    return 1e3 * s / n

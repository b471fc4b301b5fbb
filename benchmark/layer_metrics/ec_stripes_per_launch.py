"""Stripes per device launch of the aggregator the cell drives: the
encode aggregator's where the cell writes, the read aggregator's where
it reads, summed over the OSDs, over the window."""


def read(ctx, variant=None):
    fam = ctx.obs.get("agg_family")
    if fam is None:
        return None
    launches = ctx.delta.get(f"{fam}.batches", 0) + \
        ctx.delta.get(f"{fam}.bypass", 0)
    if launches <= 0:
        return None
    return ctx.delta.get(f"{fam}.stripes", 0) / launches

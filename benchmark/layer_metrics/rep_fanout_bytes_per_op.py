"""Payload bytes a primary sent to its replicas per replicated write,
over the window: the OSDs' ``rep_fanout_bytes`` over ``rep_ops``, the
driver's deltas of both. (size - 1) x the object size where every write
reaches every replica."""


def read(ctx, variant=None):
    ops = ctx.obs.get("rep_ops")
    if not ops:
        return None
    return ctx.obs.get("rep_fanout_bytes", 0) / ops

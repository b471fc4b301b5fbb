"""The least time the chip could take for the EC work that the traced
stretch's client ops required, over the time the device was busy in
that stretch, whatever ran there.

The work is read from the client's side, so it is the same whichever
engine, batch shape or kernel serves it: a write of B payload bytes
requires encoding B input bytes (``encode_bound(k, m)``); a read that
lacked a data shard requires decoding its B bytes from k chunks
(``decode_bound(1, k)``). On a v5e both bounds are HBM's. A read served
from the device shard cache delivers its work for no device time.
"""

from harness import peaks


def read(ctx, variant=None):
    ec_bytes = ctx.obs.get("traced_ec_bytes")
    if ctx.trace is None or ctx.peaks is None or not ec_bytes:
        return None
    busy = ctx.trace.busy_s("max")
    if busy <= 0:
        return None
    k, m = ctx.obs["k"], ctx.obs["m"]
    if ctx.obs["agg_family"] == "agg":
        rate, _which = peaks.encode_bound(k, m, ctx.peaks)
    else:
        rate, _which = peaks.decode_bound(1, k, ctx.peaks)
    return 100.0 * (ec_bytes / rate) / busy

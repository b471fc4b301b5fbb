"""Median wait of one replicated write for its replicas, from the
program's ``rep_subop_wait`` intervals (the primary's fan-out to the
last replica's commit reply), over the client ops wholly inside the
traced stretch. A program without that interval, as one from before
it, gives nothing to read."""

from harness import program_spans
from harness.stats import percentile

INTERVALS = {"repop": "rep_subop_wait"}


def read(ctx, variant=None):
    name = INTERVALS.get(variant)
    if name is None or ctx.trace_span is None:
        return None
    try:
        from ceph_tpu.utils import tracing
        recs = [tracing.record_dict(r) for r in tracing.captured()]
    except (ImportError, AttributeError):
        return None
    s0, s1 = (int(t * 1e9) for t in ctx.trace_span)
    inside = {r["trace_id"] for r in recs
              if r["name"] == program_spans.ROOT and r["trace_id"]
              and not r["parent_span_id"]
              and r["t0_ns"] >= s0 and r["t1_ns"] <= s1}
    per_op = {}
    for r in recs:
        if r["name"] == name and r["trace_id"] in inside:
            per_op[r["trace_id"]] = per_op.get(r["trace_id"], 0) \
                + r["t1_ns"] - r["t0_ns"]
    if not per_op:
        return None
    return percentile([ns / 1e6 for ns in per_op.values()], 50)

"""Median wait of one client op by stage, from the program's intervals
(``harness/program_spans.py``), over the ops wholly inside the traced
stretch: ``queue`` (admission to the PG's worker), ``agg`` (enqueue at
an EC aggregator to the op's result; ops that passed one only),
``subop`` (the k+m sub-writes, or the round of sub-reads)."""

from harness import program_spans
from harness.stats import percentile


def read(ctx, variant=None):
    red = program_spans.reduce(ctx)
    if red is None or not red.waits_ms.get(variant):
        return None
    return percentile(red.waits_ms[variant], 50)

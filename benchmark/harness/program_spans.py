"""The program's own spans of the traced stretch, reduced once per run.

While a ``jax.profiler`` session runs, ``ceph_tpu/utils/tracing`` keeps
every finished span and section of the process in one list
(``tracing.captured()``), stamped with ``time.perf_counter_ns()``. The
harness anchors the stretch on the same clock (``ctx.trace_span``,
seconds of ``time.perf_counter``) and on the trace's own nanoseconds
(``ctx.trace.t0_ns``, the ``bench.stretch`` annotation), so a program
stamp maps onto the trace's clock as

    trace_ns = ctx.trace.t0_ns + (stamp_ns - ctx.trace_span[0] * 1e9)

``reduce(ctx)`` returns a :class:`Reduction` (or None: nothing was
captured, records were dropped, or the program has no capture, as a
parent commit from before it) and logs, on standard error: the table of
sections (count, total, self, p50, p95), the p50/p95 of every interval
by OSD and by PG, the device's idle time in the stretch by the innermost
section covering it, the clock check and ``dropped``. The readers
``layer_metrics/op_host_ms.py`` and ``op_wait_ms.py`` share it.

A **section** is synchronous work of one thread; sections nest, so the
self time of one is its length less the sections inside it. An
**interval** is a stage of an op that may hold awaits. The layer of a
span is its name up to the first dot; the names older than that rule
are listed in ``LAYER_OF``.
"""

from __future__ import annotations

import dataclasses

from .stats import percentile

LAYERS = ("client", "msg", "osd", "ec", "store")
LAYER_OF = {"objectstore_commit": "store"}
ROOT = "client_op"
# the stage each op_wait_ms variant reads (an op's intervals of those
# names, added up)
WAITS = {"queue": ("queue",), "agg": ("ec.agg_wait",),
         "subop": ("ec_subop_wait", "osd.ec_subread_wait")}
CLOCK_CHECK_NS = 2_000_000          # the device's clock runs ~1.3 ms ahead


def layer_of(name: str) -> str:
    return LAYER_OF.get(name) or name.partition(".")[0]


@dataclasses.dataclass
class Reduction:
    ops_ended: int                  # client_op roots that ended inside
    ops_inside: int                 # ... that also started inside
    self_ns: dict                   # layer -> self ns of its sections
    cpu_ns: int                     # the recording thread's CPU time
    section_ns: int                 # all section self time, that thread
    waits_ms: dict                  # variant -> [ms per op]
    root_p50_ms: float | None       # median client_op of ops_inside
    sections: dict                  # name -> {count,total,self,p50,p95}
    skew_ns: float | None           # trace stretch less host stretch
    clock_check: float | None       # share of busy intervals matched
    idle_by: list                   # [(section name | None, seconds)]

    def host_ms(self, layer: str) -> float | None:
        if not self.ops_ended:
            return None
        if layer == "unspanned":
            return max(self.cpu_ns - self.section_ns, 0) \
                / self.ops_ended / 1e6
        return self.self_ns.get(layer, 0) / self.ops_ended / 1e6


def _records(tracing) -> list[dict]:
    return [tracing.record_dict(r) for r in tracing.captured()]


def self_segments(sections: list[dict]) -> list[tuple]:
    """[(start, end, section)] of one thread, sorted and disjoint: at
    every instant the innermost open section. Sections nest; one that
    would straddle its parent's end is cut there."""
    out, stack = [], []             # stack of [section, cursor]

    def close(upto):
        while stack and stack[-1][0]["t1_ns"] <= upto:
            sec, cur = stack.pop()
            if sec["t1_ns"] > cur:
                out.append((cur, sec["t1_ns"], sec))
            if stack:
                stack[-1][1] = max(stack[-1][1], sec["t1_ns"])

    for sec in sorted(sections, key=lambda s: (s["t0_ns"], -s["t1_ns"])):
        close(sec["t0_ns"])
        if stack:
            top, cur = stack[-1]
            if sec["t0_ns"] > cur:
                out.append((cur, sec["t0_ns"], top))
            stack[-1][1] = sec["t0_ns"]
            if sec["t1_ns"] > top["t1_ns"]:
                sec = dict(sec, t1_ns=top["t1_ns"])
        stack.append([sec, sec["t0_ns"]])
    close(float("inf"))
    return sorted(out, key=lambda seg: seg[0])


def _section_table(sections, segments) -> dict:
    table = {}
    for s in sections:
        row = table.setdefault(s["name"], {"count": 0, "total": 0,
                                           "self": 0, "each": []})
        row["count"] += 1
        row["total"] += s["t1_ns"] - s["t0_ns"]
        row["each"].append(s["t1_ns"] - s["t0_ns"])
    for a, b, s in segments:
        table[s["name"]]["self"] += b - a
    for row in table.values():
        each = row.pop("each")
        row["p50"], row["p95"] = percentile(each, 50), percentile(each, 95)
    return table


def _clock_check(ctx, to_trace, sections):
    """Share of the busiest device's busy intervals whose midpoint lies
    within 2 ms of an ``ec.launch`` .. ``ec.device_wait`` pair."""
    busy = ctx.trace.intervals.get(ctx.trace.busiest, [])
    if not busy:
        return None
    ordered = sorted((s for s in sections
                      if s["name"] in ("ec.launch", "ec.device_wait")),
                     key=lambda s: s["t0_ns"])
    pairs, launch = [], None
    for s in ordered:
        if s["name"] == "ec.launch":
            launch = s
        elif launch is not None:
            pairs.append((to_trace(launch["t0_ns"]) - CLOCK_CHECK_NS,
                          to_trace(s["t1_ns"]) + CLOCK_CHECK_NS))
            launch = None
    hit = sum(1 for a, b in busy
              if any(lo <= (a + b) / 2 <= hi for lo, hi in pairs))
    return hit / len(busy)


def _idle_by_section(ctx, to_trace, segments) -> list:
    """The busiest device's idle time inside the stretch by the
    innermost program section covering it (None: no section open)."""
    t0, t1 = ctx.trace.t0_ns, ctx.trace.t1_ns
    busy = ctx.trace.intervals.get(ctx.trace.busiest, [])
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    segs = [(to_trace(a), to_trace(b), s["name"]) for a, b, s in segments]
    by, i = {}, 0
    for ga, gb in gaps:
        covered = 0
        while i < len(segs) and segs[i][1] <= ga:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < gb:
            a, b, name = segs[j]
            part = min(b, gb) - max(a, ga)
            if part > 0:
                by[name] = by.get(name, 0) + part
                covered += part
            j += 1
        by[None] = by.get(None, 0) + (gb - ga) - covered
    return sorted(((n, ns / 1e9) for n, ns in by.items()),
                  key=lambda kv: -kv[1])


def _log_intervals(ctx, intervals) -> None:
    """p50/p95 of every interval by OSD and by PG (the PG is the
    ``osd_op``'s ``pgid`` tag, shared by the spans of its trace)."""
    pg_of = {s["trace_id"]: s["tags"].get("pgid") for s in intervals
             if s["name"] == "osd_op" and s["trace_id"]}
    for what, key in (("osd", lambda s: s["service"]),
                      ("pg", lambda s: pg_of.get(s["trace_id"]))):
        groups = {}
        for s in intervals:
            k = key(s)
            if k is not None and s["name"] != ROOT:
                groups.setdefault((s["name"], k), []).append(
                    (s["t1_ns"] - s["t0_ns"]) / 1e6)
        for name in sorted({n for n, _k in groups}):
            rows = " ".join(
                f"{k}:{len(v)}/{percentile(v, 50):.1f}/"
                f"{percentile(v, 95):.1f}"
                for (n, k), v in sorted(groups.items(),
                                        key=lambda kv: str(kv[0][1]))
                if n == name)
            ctx.log(f"spans interval {name} by {what} "
                    f"(n/p50/p95 ms): {rows}")


def _log_op_stages(ctx, inside, intervals) -> None:
    """Where an op's latency lies around the OSD: from the client's
    submit to the primary's admission (``osd_op`` opens), inside
    ``osd_op``, and from its end to the client's return."""
    osd_op = {}
    for s in intervals:
        if s["name"] == "osd_op":
            osd_op.setdefault(s["trace_id"], s)     # the first attempt
    stages = {"to_osd": [], "osd_op": [], "reply": []}
    for r in inside:
        o = osd_op.get(r["trace_id"])
        if o is not None:
            stages["to_osd"].append((o["t0_ns"] - r["t0_ns"]) / 1e6)
            stages["osd_op"].append((o["t1_ns"] - o["t0_ns"]) / 1e6)
            stages["reply"].append((r["t1_ns"] - o["t1_ns"]) / 1e6)
    if stages["osd_op"]:
        ctx.log("spans op stages (p50/p95 ms): " + " ".join(
            f"{k}={percentile(v, 50):.1f}/{percentile(v, 95):.1f}"
            for k, v in stages.items()))


def reduce(ctx) -> Reduction | None:
    """The reduction of this run's capture, computed once."""
    if not hasattr(ctx, "_program_spans"):
        ctx._program_spans = _reduce(ctx)
    return ctx._program_spans


def _reduce(ctx) -> Reduction | None:
    try:
        from ceph_tpu.utils import tracing
        recs, info = _records(tracing), tracing.capture_info()
    except (ImportError, AttributeError):
        ctx.log("spans: this program has no capture")
        return None
    if ctx.trace_span is None or not recs:
        ctx.log(f"spans: nothing captured ({len(recs)} records)")
        return None
    ctx.log(f"spans: {len(recs)} records, dropped {info['dropped']}")
    if info["dropped"]:
        return None
    s0, s1 = (int(t * 1e9) for t in ctx.trace_span)
    sections = [r for r in recs if r["kind"] == "section"]
    intervals = [r for r in recs if r["kind"] != "section"]
    # the thread with the most section time: the one event loop
    by_thread = {}
    for s in sections:
        by_thread[s["thread"]] = by_thread.get(s["thread"], 0) \
            + s["t1_ns"] - s["t0_ns"]
    if not by_thread:
        ctx.log("spans: no section captured")
        return None
    thread = max(by_thread, key=by_thread.get)
    mine = [s for s in sections if s["thread"] == thread
            and s["t0_ns"] >= s0 and s["t1_ns"] <= s1]
    segments = self_segments(mine)
    self_ns = {}
    for a, b, s in segments:
        lay = layer_of(s["name"])
        self_ns[lay] = self_ns.get(lay, 0) + b - a
    th = info["threads"].get(thread)
    cpu_ns = th["cpu_ns"][1] - th["cpu_ns"][0] if th else 0
    # the sections the two CPU stamps enclose
    section_ns = sum(b - a for a, b, s in segments if th and
                     th["clock_ns"][0] < s["t1_ns"] <= th["clock_ns"][1])
    roots = [r for r in intervals if r["name"] == ROOT
             and not r["parent_span_id"] and s0 <= r["t1_ns"] <= s1]
    inside = [r for r in roots if r["t0_ns"] >= s0]
    # an op's waits: its intervals of those names, added up
    ids = {r["trace_id"] for r in inside if r["trace_id"]}
    per_op = {}
    for s in intervals:
        if s["trace_id"] in ids:
            d = per_op.setdefault(s["trace_id"], {})
            d[s["name"]] = d.get(s["name"], 0) + s["t1_ns"] - s["t0_ns"]
    waits = {v: [sum(d[n] for n in names if n in d) / 1e6
                 for d in per_op.values() if any(n in d for n in names)]
             for v, names in WAITS.items()}
    root_ms = [(r["t1_ns"] - r["t0_ns"]) / 1e6 for r in inside]
    table = _section_table(mine, segments)
    skew = check = None
    idle = []
    if ctx.trace is not None:
        base = ctx.trace.t0_ns - s0

        def to_trace(stamp_ns):
            return stamp_ns + base

        skew = (ctx.trace.t1_ns - ctx.trace.t0_ns) - (s1 - s0)
        check = _clock_check(ctx, to_trace, mine)
        idle = _idle_by_section(ctx, to_trace, segments)
    red = Reduction(
        ops_ended=len(roots), ops_inside=len(inside), self_ns=self_ns,
        cpu_ns=cpu_ns, section_ns=section_ns, waits_ms=waits,
        root_p50_ms=percentile(root_ms, 50) if root_ms else None,
        sections=table, skew_ns=skew, clock_check=check, idle_by=idle)
    _log(ctx, red, intervals, len(by_thread))
    _log_op_stages(ctx, inside, intervals)
    return red


def _log(ctx, red: Reduction, intervals, n_threads: int) -> None:
    ctx.log(f"spans: {red.ops_ended} client ops ended in the stretch, "
            f"{red.ops_inside} wholly inside; sections on "
            f"{n_threads} thread(s)")
    ctx.log("spans section: count total_ms self_ms p50_us p95_us")
    for name, row in sorted(red.sections.items(),
                            key=lambda kv: -kv[1]["self"]):
        ctx.log(f"spans section {name}: {row['count']} "
                f"{row['total'] / 1e6:.1f} {row['self'] / 1e6:.1f} "
                f"{row['p50'] / 1e3:.0f} {row['p95'] / 1e3:.0f}")
    _log_intervals(ctx, intervals)
    if red.ops_ended:
        host = {lay: red.host_ms(lay) for lay in LAYERS + ("unspanned",)}
        total = sum(host.values())
        ctx.log("spans op_host_ms " + " ".join(
            f"{k}={v:.2f}" for k, v in host.items())
            + f" sum={total:.2f}; x {red.ops_ended} ops = "
            f"{total * red.ops_ended / 1e3:.3f} s against the thread's "
            f"CPU {red.cpu_ns / 1e9:.3f} s over the capture")
    if red.root_p50_ms is not None:
        lat = ctx.obs.get("op_lat_s")
        p50 = f"{percentile(lat, 50) * 1e3:.1f}" if lat else "?"
        ctx.log(f"spans client_op p50 {red.root_p50_ms:.1f} ms over the "
                f"ops inside the stretch; the window's op_p50_ms {p50}")
        if ctx.trace is not None:
            # the same ops on the driver's side: its annotations that
            # lie wholly inside the stretch, on the trace's clock
            drv = [(b - a) / 1e6 for n, a, b in ctx.trace.host_spans
                   if n != "bench.stretch" and a >= ctx.trace.t0_ns
                   and b <= ctx.trace.t1_ns]
            if drv:
                ctx.log(f"spans the driver's own spans inside the stretch: "
                        f"{len(drv)}, p50 {percentile(drv, 50):.1f} ms")
    if red.skew_ns is not None:
        ctx.log(f"spans clocks: the trace's stretch is "
                f"{red.skew_ns / 1e6:+.3f} ms longer than the host's")
        if red.clock_check is not None:
            ctx.log(f"spans clock check: {100 * red.clock_check:.1f}% of "
                    f"the device's busy intervals lie within 2 ms of an "
                    f"ec.launch..ec.device_wait pair")
        ctx.log("spans device idle by innermost section (s): "
                + " ".join(f"{n or 'no_section'}={s:.3f}"
                           for n, s in red.idle_by[:12]))

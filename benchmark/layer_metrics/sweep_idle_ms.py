"""The busiest device's idle time in the traced stretch, put down to
what the placement path's thread was doing: the innermost ``crush.*``
section open at that instant (variants ``test``, ``sweep``,
``dispatch``, ``force``, ``readback``; the program's sections, PR 37),
``other`` where none was (the caller's loop: in the pod cell the
driver's own read-back). Each is ms over the sweep calls that ended in
the stretch, a sweep call being an outermost ``crush.*`` section, so
the six times that count are the idle time behind
``device_idle_pct.crush``.

The program's stamps map onto the trace through the ``bench.stretch``
anchor (``harness/program_spans.py``), but the device's own stamps are
off the host's by d, about a millisecond (a fifth of the gap to be
split), so d is bracketed from the program's sync points. The busy
intervals merge into bursts across gaps under ``BURST_GAP_NS`` (one
program's operations, or programs queued back to back; a program the
host dispatched after its own work is a burst of its own), and the
stretch is cut into epochs at the end of every sync (``crush.force``,
``crush.readback``, and the driver's ``bench.sweep``, which ends in
its read-back). A burst belongs to the epoch its midpoint falls in
once moved back by the estimate; of each epoch that holds a
``crush.dispatch`` the longest burst is its block, and

- no device operation of a block starts before its dispatch began:
  d <= the burst's start - the epoch's first dispatch;
- a sync ends after the last operation it waited for:
  d >= the burst's end - the sync's end.

Epochs with no dispatch are left out: their bursts are the small
programs the host runs between dispatches (``jnp.zeros``, a slice of
the counts), which a wrong estimate puts in the wrong epoch. The
tightest bracket over the stretch (estimate 0, then the first
bracket's midpoint) is logged; the idle gaps move back by its midpoint
before any is put down to a section. An empty bracket reads None: the
clocks cannot be reconciled. So does a stretch with no ``crush.sweep``
(a tree from before PR 37)."""

import bisect
import math

from harness import program_spans

STAGES = ("test", "sweep", "dispatch", "force", "readback")
SYNCS = ("crush.force", "crush.readback")
# inside one program the device's operations follow one another within
# microseconds; between two programs lies the host's own work
BURST_GAP_NS = 50_000


def read(ctx, variant=None):
    if not hasattr(ctx, "_sweep_idle"):
        ctx._sweep_idle = _reduce(ctx)
    split = ctx._sweep_idle
    return None if split is None else split[variant]


def bursts(busy) -> list:
    """``busy`` (sorted, disjoint) merged across gaps under
    ``BURST_GAP_NS``."""
    out = []
    for a, b in busy:
        if out and a - out[-1][1] < BURST_GAP_NS:
            out[-1][1] = b
        else:
            out.append([a, b])
    return out


def bracket(blocks, ends, starts, guess=0.0):
    """(lo, hi) of d, ns. ``blocks``: the device's bursts, sorted;
    ``ends``: the syncs' ends and ``starts`` the dispatches' starts,
    sorted, on the host's side of the trace clock."""
    mids = [(a + b) / 2 for a, b in blocks]
    lo, hi, prev = -math.inf, math.inf, -math.inf
    for end in ends:
        i = bisect.bisect_right(mids, prev + guess)
        j = bisect.bisect_right(mids, end + guess)
        k = bisect.bisect_right(starts, prev)
        if j > i and k < len(starts) and starts[k] <= end:
            a, b = max(blocks[i:j], key=lambda iv: iv[1] - iv[0])
            lo, hi = max(lo, b - end), min(hi, a - starts[k])
        prev = end
    return lo, hi


def _outermost(sections) -> int:
    n, reach = 0, -math.inf
    for s in sorted(sections, key=lambda s: (s["t0_ns"], -s["t1_ns"])):
        if s["t0_ns"] >= reach:
            n += 1
        reach = max(reach, s["t1_ns"])
    return n


def _reduce(ctx):
    if program_spans.reduce(ctx) is None or ctx.trace is None:
        return None
    from ceph_tpu.utils import tracing
    s0, s1 = (int(t * 1e9) for t in ctx.trace_span)
    crush = [r for r in program_spans._records(tracing)
             if r["kind"] == "section" and r["name"].startswith("crush.")]
    by_thread = {}
    for s in crush:
        by_thread[s["thread"]] = by_thread.get(s["thread"], 0) \
            + s["t1_ns"] - s["t0_ns"]
    thread = max(by_thread, key=by_thread.get, default=None)
    mine = [s for s in crush if s["thread"] == thread
            and s["t0_ns"] >= s0 and s["t1_ns"] <= s1]
    if not any(s["name"] == "crush.sweep" for s in mine):
        ctx.log("sweep_idle: no crush.sweep section in the stretch")
        return None
    n = _outermost(mine)
    base = ctx.trace.t0_ns - s0
    ends = sorted([s["t1_ns"] + base for s in mine if s["name"] in SYNCS]
                  + [b for _a, b in ctx.trace.spans_named("bench.sweep")])
    starts = sorted(s["t0_ns"] for s in mine
                    if s["name"] == "crush.dispatch")
    busy = ctx.trace.intervals.get(ctx.trace.busiest, [])
    segments = program_spans.self_segments(mine)
    _log_host(ctx, segments, starts, n,
              tracing.capture_info()["threads"].get(thread))
    starts = [t + base for t in starts]
    blocks = bursts(busy)
    gaps = [b[0] - a[1] for a, b in zip(busy, busy[1:])]
    ctx.log("sweep_idle gaps between busy intervals (us: count): "
            + " ".join(f"<{edge // 1000}: {sum(a <= g < edge for g in gaps)}"
                       for a, edge in zip((0, 5_000, 50_000, 500_000),
                                          (5_000, 50_000, 500_000, 10**12))))
    first = bracket(blocks, ends, starts)
    lo, hi = bracket(blocks, ends, starts,
                     (first[0] + first[1]) / 2
                     if -math.inf < first[0] <= first[1] < math.inf else 0.0)
    ctx.log(f"sweep_idle: {n} sweeps in the stretch, {len(ends)} syncs, "
            f"{len(starts)} dispatches, {len(busy)} busy intervals in "
            f"{len(blocks)} bursts; the device's stamps are off the "
            f"host's by d in [{lo / 1e6:+.4f}, {hi / 1e6:+.4f}] ms (at "
            f"estimate 0: [{first[0] / 1e6:+.4f}, {first[1] / 1e6:+.4f}])")
    if not -math.inf < lo <= hi < math.inf:
        ctx.log("sweep_idle: the bracket is empty or open: the clocks "
                "cannot be reconciled")
        return None
    mid = (lo + hi) / 2
    split = _split(ctx, segments, base + mid, n)
    total = sum(split.values())
    stretch_idle = (ctx.trace.window_ns
                    - ctx.trace.busy_s("max") * 1e9) / 1e9
    ctx.log(f"sweep_idle_ms at d = {mid / 1e6:+.4f} ms: " + " ".join(
        f"{k}={v:.4f}" for k, v in split.items())
        + f" sum={total:.4f}; x {n} sweeps = {total * n / 1e3:.6f} s "
        f"against the stretch's idle {stretch_idle:.6f} s")
    for end in (lo, hi):
        ctx.log(f"sweep_idle_ms at d = {end / 1e6:+.4f} ms: " + " ".join(
            f"{k}={v:.4f}"
            for k, v in _split(ctx, segments, base + end, n).items()))
    return split


def _split(ctx, segments, shift, n) -> dict:
    """ms of idle a sweep by stage, the program's stamps moved onto the
    trace's clock by ``shift``."""
    split = dict.fromkeys(STAGES + ("other",), 0.0)
    for name, sec in program_spans._idle_by_section(
            ctx, lambda stamp: stamp + shift, segments):
        stage = (name or "").partition(".")[2]
        split[stage if stage in STAGES else "other"] += 1e3 * sec / n
    return split


def _log_host(ctx, segments, starts, n, thread_info) -> None:
    """Each stage's wall self time a sweep (``crush.sweep``'s before its
    first dispatch, the prelude, and after it), and the thread's CPU."""
    self_ns = dict.fromkeys(STAGES, 0)
    prelude = 0
    for a, b, s in segments:
        stage = s["name"].partition(".")[2]
        self_ns[stage] = self_ns.get(stage, 0) + b - a
        if stage == "sweep":
            k = bisect.bisect_left(starts, s["t0_ns"])
            if k == len(starts) or b <= starts[k]:
                prelude += b - a
    cpu = thread_info["cpu_ns"][1] - thread_info["cpu_ns"][0] \
        if thread_info else 0
    ctx.log("sweep_idle wall self a sweep (ms): " + " ".join(
        f"{k}={v / n / 1e6:.4f}" for k, v in self_ns.items())
        + f" (sweep's prelude {prelude / n / 1e6:.4f}); the driving "
        f"thread's CPU a sweep over the capture {cpu / n / 1e6:.4f}")

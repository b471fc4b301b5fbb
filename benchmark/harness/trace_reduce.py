"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers: per-device
busy time, the busy union, the device operations that took most time and
the longest idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the
device planes are ``/device:TPU:<n>``; the line ``XLA Ops`` holds one
event for each operation that ran on the core, with its start and
duration in nanoseconds; ``XLA Modules`` holds one for each program.
Busy time is the union of the ``XLA Ops`` intervals (of the module line
where a plane has no op line), cut to the stretch asked for. Host
annotations (``jax.profiler.TraceAnnotation``) are on the host plane's
thread lines, on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
_LAYOUT = re.compile(r"\{[^}]*\}")
_KIND = re.compile(r"kind=(\w+)")
OP_LINES = ("XLA Ops", "XLA Modules")


@dataclasses.dataclass
class TraceSummary:
    window_ns: int                      # length of the stretch reduced
    busy_ns: dict                       # device ordinal -> busy ns
    ops: list                           # [(name, seconds)], busiest dev
    gaps: list                          # [(host span name, seconds)]
    events: int                         # device op events counted
    t0_ns: int = 0
    t1_ns: int = 0
    intervals: dict = dataclasses.field(default_factory=dict, repr=False)
    host_spans: list = dataclasses.field(default_factory=list, repr=False)

    def spans_named(self, name: str) -> list:
        """[(start_ns, end_ns)] of the host spans of that name that lie
        wholly inside the stretch."""
        return [(a, b) for n, a, b in self.host_spans
                if n == name and a >= self.t0_ns and b <= self.t1_ns]

    @property
    def busiest(self) -> int:
        return max(self.busy_ns, key=lambda d: self.busy_ns[d])

    def busy_s(self, how: str = "mean") -> float:
        vals = list(self.busy_ns.values())
        ns = {"mean": sum(vals) / len(vals), "max": max(vals),
              "min": min(vals)}[how]
        return ns / 1e9

    def busy_within(self, spans, device: int | None = None) -> float:
        """Seconds the device (default: the busiest) was busy inside
        the host spans [(start_ns, end_ns)] given."""
        iv = self.intervals[self.busiest if device is None else device]
        total = 0
        for a, b in spans:
            for s, e in iv:
                if e <= a:
                    continue
                if s >= b:
                    break
                total += min(e, b) - max(s, a)
        return total / 1e9


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def _op_name(text: str) -> str:
    """``%fusion.3 = u8[8,64]{1,0:T(8,128)} fusion(...), kind=kLoop`` ->
    ``fusion.3 u8[8,64] fusion kLoop``: the operation's name, what it
    makes (of a tuple the first part) and what it is, so that a name in a breakdown says something
    without the program's text."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name[:96]
    if rest.startswith("("):            # a tuple: cut at its closing
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        first = _LAYOUT.sub("", rest[1:i]).partition(", ")[0]
        made, rest = f"({first},..)", rest[i + 1:].lstrip()
    else:
        made, _, rest = rest.partition(" ")
        made = _LAYOUT.sub("", made)
    what = rest.partition("(")[0]
    kind = _KIND.search(rest)
    parts = [name, made, what] + ([kind.group(1)] if kind else [])
    return " ".join(parts)[:96]


def _device_lines(plane):
    lines = {ln.name: ln for ln in plane.lines}
    for name in OP_LINES:
        if name in lines:
            return lines[name]
    return None


def reduce_trace(path: str, span_name: str | None = None,
                 host_prefix: str = "bench.") -> TraceSummary:
    """Reduce the trace at ``path`` (a file or the directory the
    profiler wrote). The stretch is the host span named ``span_name``
    where given (the benchmark puts one around the traced stretch),
    else from the first to the last device event."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = jax.profiler.ProfileData.from_file(path)
    dev_events, host_spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            line = _device_lines(plane)
            if line is None:
                continue
            ordinal = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            dev_events[ordinal] = [
                (_op_name(e.name), int(e.start_ns),
                 int(e.start_ns + e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        host_spans.append(
                            (e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    if not dev_events:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane with an op "
                         f"line; planes: {[p.name for p in pd.planes]}")
    stretch = [s for s in host_spans if s[0] == span_name]
    if span_name and stretch:
        t0, t1 = stretch[0][1], stretch[0][2]
    else:
        every = [e for evs in dev_events.values() for e in evs]
        if not every:
            raise ValueError(f"{path}: no operation ran on a device")
        t0 = min(e[1] for e in every)
        t1 = max(e[2] for e in every)
    busy, merged = {}, {}
    for dev, evs in dev_events.items():
        merged[dev] = _clip(_union((s, e) for _n, s, e in evs), t0, t1)
        busy[dev] = sum(e - s for s, e in merged[dev])
    top = max(busy, key=lambda d: busy[d])
    by_name = {}
    n_events = 0
    for name, self_ns in _self_times(
            [(n, max(s, t0), min(e, t1)) for n, s, e in dev_events[top]
             if e > t0 and s < t1]):
        by_name[name] = by_name.get(name, 0) + self_ns
        n_events += 1
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = _gaps(merged[top], t0, t1,
                 [s for s in host_spans if s[0] != span_name])
    return TraceSummary(
        window_ns=t1 - t0, busy_ns=busy, events=n_events, t0_ns=t0,
        t1_ns=t1, ops=[(n, ns / 1e9) for n, ns in ops],
        gaps=gaps, intervals=merged, host_spans=host_spans)


def _self_times(events):
    """(name, self ns) of each event: its length less the events nested
    in it (a ``while`` holds its body's operations on the same line)."""
    out, stack = [], []                 # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((n, ns) for n, _e, ns in stack)
    return out


def _gaps(busy_intervals, t0, t1, host_spans):
    """Idle time of the busiest device by the host span that covers the
    middle of each gap (the innermost one), summed by name, longest
    first. A gap that no span of the benchmark covers is
    ``outside_bench_spans``."""
    edges = [t0] + [t for iv in busy_intervals for t in iv] + [t1]
    total = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        covering = [s for s in host_spans if s[1] <= mid < s[2]]
        name = min(covering, key=lambda s: s[2] - s[1])[0] \
            if covering else "outside_bench_spans"
        total[name] = total.get(name, 0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:10]
    return [(n, ns / 1e9) for n, ns in ranked]

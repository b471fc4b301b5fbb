"""Distributed op tracing: Tracer/Span core + cross-daemon reassembly.

TPU-native analog of Ceph's tracing layer (ref: src/common/tracer.{h,cc}
— the Jaeger/blkin integration whose trace context rides MOSDOp so one
client op can be decomposed into queue / replica / store time across
daemons). A ``Span`` is one timed phase inside one daemon; spans of one
logical op share a ``trace_id`` and link through ``parent_span_id``, and
the context crosses message boundaries as two u64s appended to every
wire ``Message`` (zero = untraced).

Two kinds of span, one type:

- an **interval** is a stage in an op's life that may hold ``await``s
  (``queue``, ``ec_subop_wait``, ``ec.agg_wait``): intervals of
  different ops overlap freely, so an interval's length is a WAIT, not
  anybody's CPU time;
- a **section** (:func:`section`, a context manager) is synchronous
  work on the recording thread with no ``await`` inside. Sections nest
  and never overlap otherwise, so the innermost open section at any
  instant is what the thread is doing, self time (a section less the
  sections inside it) is well defined, and the sum over all sections
  is at most the thread's CPU time.

One clock: both ends of every span are ``time.perf_counter_ns()``
stamps (``t0_ns``/``t1_ns``); the wall ``start`` that ``dump()`` and
``TraceIndex`` show is derived from one process-wide offset taken at
import. ``jax.profiler`` host events are on the same clock, which is
how a benchmark lays spans over the device's busy intervals.

Sampling model:

- **head-based**: ``trace_sampling_rate`` decides at the op's root
  (client side) whether the trace gets a nonzero trace_id and therefore
  propagates downstream;
- **tail-based retention for slow ops**: an UNSAMPLED root is still
  timed locally (one Span object, no propagation), and if its duration
  crosses ``trace_slow_keep_s`` it is assigned a trace id post-hoc and
  kept in the slow buffer — SLOW_OPS warnings stay drill-downable even
  at sampling 0. ``trace_slow_keep_s <= 0`` disables even this local
  timing (the truly-off path the bench pins);
- **capture**: while a ``jax.profiler`` session runs in the process
  (:func:`capturing`), every ``client_op`` root is sampled as if the
  rate were 1 — under a trace id with :data:`CAPTURE_ONLY` set, so no
  daemon ships it to the operator's buffers — and every finished span
  and section is appended as a tuple to one bounded process-wide list
  (:func:`captured`). Sections are captured whether or not their op was
  sampled, so ops already in flight when the session starts leave no
  holes. No option turns this on: take a device trace, and the
  program's spans of those seconds are there, on its clock. With no
  session and sampling 0 a section costs that one check and allocates
  nothing.

Names: the spans older than the sections keep theirs (``client_op``,
``osd_op``, ``queue``, ``execute``, ``ec_subop_wait``, ``ec_sub_write``,
``objectstore_commit``, ...); new ones are ``<layer>.<what>`` with the
layer (``client``, ``msg``, ``osd``, ``ec``, ``store``) as the prefix.

Completed spans of sampled ops land in a bounded per-daemon buffer
(asok ``dump_tracing``) and a bounded ship queue the daemon's existing
reporting loop drains monward (MPGStats / MDSBeacon piggyback,
MTraceReport for clients); the mon pools them and the mgr
TracingModule reassembles cross-daemon traces by trace_id
(``ceph trace ls`` / ``ceph trace show <trace_id>``).
"""

from __future__ import annotations

import itertools
import json
import random
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Any

_clock = time.perf_counter_ns
# wall seconds at perf_counter_ns() == 0: the one offset every span's
# ``start`` is derived from (cross-daemon alignment stays on the wall)
_WALL_OFFSET = time.time() - _clock() / 1e9

INTERVAL = "interval"
SECTION = "section"

# set in a trace id minted because a profiler session was running, not
# because the operator's sampling chose the op: every daemon the context
# reaches can tell, and keeps such spans out of its buffers
CAPTURE_ONLY = 1 << 62


def new_trace_id() -> int:
    """Nonzero 62-bit id (0 is the 'untraced' sentinel on the wire,
    bit 62 is :data:`CAPTURE_ONLY`)."""
    return random.getrandbits(62) | 1


# span ids: odd numbers counted up from a random 62-bit start, unique
# within the process and as good as random across processes, at a
# quarter of getrandbits' cost (a captured op makes hundreds of spans)
_span_ids = itertools.count(new_trace_id(), 2)
_ID_MASK = (1 << 62) - 1
_flatten = itertools.chain.from_iterable


def wall_of(stamp_ns: int) -> float:
    """Wall-clock seconds of a ``perf_counter_ns`` stamp."""
    return _WALL_OFFSET + stamp_ns / 1e9


# -- capture -----------------------------------------------------------------

# what one captured record holds, in order; the span's tags follow as
# key, value, key, value (one flat tuple of atoms a record: the
# collector stops tracking it, and a session keeps tens of thousands)
FIELDS = ("kind", "name", "service", "thread", "t0_ns", "t1_ns",
          "trace_id", "span_id", "parent_span_id")
CAPTURE_MAX = 1 << 19       # records kept per session; the rest count

_probe = None               # jax.profiler.TraceAnnotation.is_enabled
_cap_on = False
_cap_records: list[tuple] = []
_cap_dropped = 0
# thread id -> [cpu first, clock first, cpu last, clock last] (ns): the
# thread's CPU time beside the clock at its first section and (stamped
# at most once a millisecond: the call is a system call) at its last
_cap_threads: dict[int, list[int]] = {}
_CPU_STAMP_NS = 1_000_000


def _find_probe():
    jax = sys.modules.get("jax")
    if jax is None:                       # never imported for our sake
        return None
    try:
        return jax.profiler.TraceAnnotation.is_enabled
    except AttributeError:                # jax still importing, or old
        return None


def capturing() -> bool:
    """Is a ``jax.profiler`` session running in this process? Looked
    up where the work happens; a new session starts a new capture."""
    global _probe, _cap_on, _cap_records, _cap_dropped, _cap_threads
    probe = _probe
    if probe is None:
        probe = _probe = _find_probe()
        if probe is None:
            return False
    on = probe()
    if on is not _cap_on:
        if on:
            _cap_records, _cap_dropped, _cap_threads = [], 0, {}
        _cap_on = on
    return on


def _capture(kind, name, service, thread, t0_ns, t1_ns, trace_id,
             span_id, parent_span_id, tags) -> None:
    global _cap_dropped
    if len(_cap_records) >= CAPTURE_MAX:
        _cap_dropped += 1
        return
    rec = (kind, name, service, thread, t0_ns, t1_ns, trace_id, span_id,
           parent_span_id)
    if tags:
        rec += tuple(_flatten(tags.items()))
    _cap_records.append(rec)
    if thread:                            # a section, on its own thread
        ent = _cap_threads.get(thread)
        if ent is None:
            cpu, now = time.thread_time_ns(), _clock()
            _cap_threads[thread] = [cpu, now, cpu, now]
        elif t1_ns - ent[3] > _CPU_STAMP_NS:
            ent[2], ent[3] = time.thread_time_ns(), _clock()


def captured() -> list[tuple]:
    """The records of the newest profiler session, each a tuple in the
    order of :data:`FIELDS` with the tags after them. Kept until the
    next session starts."""
    return _cap_records


def record_dict(rec: tuple) -> dict:
    """One captured record as a dict, ``tags`` among its keys."""
    n = len(FIELDS)
    d = dict(zip(FIELDS, rec))
    d["tags"] = dict(zip(rec[n::2], rec[n + 1::2]))
    return d


def capture_info() -> dict:
    """``dropped`` (records beyond :data:`CAPTURE_MAX`) and, for every
    thread that recorded a section, its ``time.thread_time_ns()`` and
    ``time.perf_counter_ns()`` at the first and at the last one (the
    last to within a millisecond)."""
    return {"dropped": _cap_dropped, "records": len(_cap_records),
            "threads": {th: {"cpu_ns": (e[0], e[2]),
                             "clock_ns": (e[1], e[3])}
                        for th, e in _cap_threads.items()}}


class Span:
    """One timed phase inside one daemon (ref: a jspan/blkin trace
    point pair), an interval or a section (``kind``). ``trace_id == 0``
    marks a local-only root still awaiting the tail-retention decision,
    or a section captured for an op nobody sampled."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_span_id",
                 "name", "service", "kind", "thread", "t0_ns", "t1_ns",
                 "duration", "tags", "finished")

    def __init__(self, tracer: "Tracer | None", name: str,
                 trace_id: int, parent_span_id: int = 0,
                 tags: dict | None = None, kind: str = INTERVAL,
                 service: str = ""):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = next(_span_ids) & _ID_MASK
        self.parent_span_id = parent_span_id
        self.name = name
        self.service = tracer.service if tracer is not None else service
        self.kind = kind
        self.thread = threading.get_ident() if kind == SECTION else 0
        self.t1_ns: int | None = None
        self.duration: float | None = None
        self.tags: dict = dict(tags) if tags else {}
        self.finished = False
        self.t0_ns = _clock()

    @property
    def start(self) -> float:
        """Wall seconds of ``t0_ns`` (cross-daemon alignment)."""
        return wall_of(self.t0_ns)

    def tag(self, key: str, value: Any) -> "Span":
        self.tags[key] = value
        return self

    def child(self, name: str, tags: dict | None = None) -> "Span":
        """A child interval in the SAME daemon (same trace, linked)."""
        return Span(self.tracer, name, self.trace_id,
                    parent_span_id=self.span_id, tags=tags)

    def annotate(self, name: str, duration: float,
                 tags: dict | None = None) -> None:
        """Record an already-measured sub-phase as a FINISHED child
        span (the kv/WAL split: synchronous store code times its own
        phases and the caller attaches them post-hoc — a live child
        span would double-count the enclosing wall)."""
        s = self.child(name, tags=tags)
        # start back-dated so the child nests inside this span's wall
        s.close_at(self.t0_ns,
                   self.t0_ns + int(max(float(duration), 0.0) * 1e9))

    def close_at(self, t0_ns: int, t1_ns: int) -> None:
        """Finish with both stamps given (a phase measured before its
        span could be made: the context was not known yet)."""
        self.finished = True
        self.t0_ns, self.t1_ns = t0_ns, t1_ns
        self.duration = (t1_ns - t0_ns) / 1e9
        _finished(self)

    def finish(self) -> None:
        if self.finished:
            return
        self.finished = True
        self.t1_ns = t1 = _clock()
        self.duration = (t1 - self.t0_ns) / 1e9
        _finished(self)

    # a section is used as ``with tracing.section(...) as s:``
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()

    def dump(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_span_id": self.parent_span_id,
            "name": self.name,
            "service": self.service,
            "kind": self.kind,
            "start": self.start,
            "duration": round(
                self.duration if self.duration is not None
                else (_clock() - self.t0_ns) / 1e9, 9),
            "tags": self.tags,
        }


def _finished(span: Span) -> None:
    if capturing():
        _capture(span.kind, span.name, span.service, span.thread,
                 span.t0_ns, span.t1_ns, span.trace_id, span.span_id,
                 span.parent_span_id, span.tags)
    tid = span.trace_id
    if span.tracer is not None and (
            (tid and not tid & CAPTURE_ONLY) or
            not (span.parent_span_id or span.thread)):
        # the operator's op, or a root that tail retention may keep
        span.tracer.record(span)


class _Off:
    """What :func:`section` returns when nobody is looking: one shared
    object, nothing allocated, every method a no-op."""

    __slots__ = ()
    trace_id = span_id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def __bool__(self):
        return False

    def tag(self, key, value):
        return self

    def finish(self):
        return None


_OFF = _Off()


def _context(ctx, tracer):
    """(trace id, parent span id, tracer) of ``ctx``: a Span (the new
    span becomes its child), a wire Message (it becomes a child of the
    sender's span) or None."""
    if ctx is None:
        return 0, 0, tracer
    if isinstance(ctx, Span):
        return ctx.trace_id, ctx.span_id, \
            tracer if tracer is not None else ctx.tracer
    return ctx.trace_id, ctx.parent_span_id, tracer


def wanted(ctx=None, tracer=None) -> bool:
    """Would a section under ``ctx`` be recorded anywhere? True when
    the op was sampled for the operator and there is a tracer to keep
    it, or while a profiler session runs. This is the off path: the
    one check, nothing allocated."""
    tid = ctx.trace_id if ctx is not None else 0
    return bool(tid and not tid & CAPTURE_ONLY
                and (tracer is not None or isinstance(ctx, Span))) \
        or capturing()


def section(name: str, ctx=None, tracer: "Tracer | None" = None,
            service: str = ""):
    """Open a section: ``with tracing.section("msg.encode", msg,
    tracer) as s: ...; s.tag("bytes", n)``. No ``await`` inside (close
    it with ``s.finish()`` before a branch that awaits). ``ctx`` is the
    parent Span, the wire Message that carries the context, or None;
    ``service`` names the recorder where there is no tracer."""
    # wanted(), spelt out: this is the call every site makes
    tid = ctx.trace_id if ctx is not None else 0
    if not ((tid and not tid & CAPTURE_ONLY
             and (tracer is not None or isinstance(ctx, Span)))
            or capturing()):
        return _OFF
    tid, pid, tracer = _context(ctx, tracer)
    return Span(tracer, name, tid, pid, None, SECTION, service)


def emit_section(name: str, t0_ns: int, t1_ns: int, ctx=None,
                 tracer: "Tracer | None" = None, service: str = "",
                 tags: dict | None = None) -> None:
    """A section measured before its context was known (a frame is
    checked and decoded before the message says whose op it is). The
    caller asks :func:`wanted` first."""
    tid, pid, tracer = _context(ctx, tracer)
    if not tid or tid & CAPTURE_ONLY or tracer is None:
        # nobody's buffers want it, only the capture: no Span is made
        # (a session sees four of these for every message)
        if capturing():
            _capture(SECTION, name,
                     tracer.service if tracer is not None else service,
                     threading.get_ident(), t0_ns, t1_ns, tid,
                     next(_span_ids) & _ID_MASK, pid, tags)
        return
    s = Span(tracer, name, tid, pid, tags, SECTION, service)
    s.close_at(t0_ns, t1_ns)


class Tracer:
    """Per-daemon span factory + bounded completed-span buffers.

    Knobs are read LIVE from the daemon's config dict (falling back to
    the registered utils.config defaults), so `config set` style
    runtime changes apply to the next op."""

    def __init__(self, service: str, config: dict | None = None):
        self.service = service
        self.config = config if config is not None else {}
        self._buf: deque[dict] = deque(maxlen=self._buffer_size())
        # slow spans survive fast-op churn in their own bounded ring
        self._slow: deque[dict] = deque(maxlen=64)
        # pending shipment to the mon (piggybacked on the daemon's
        # existing report loop); bounded — observability must never
        # become the memory leak it exists to find
        self._shipq: deque[bytes] = deque(maxlen=1024)

    # -- knobs -------------------------------------------------------------
    def _get(self, name: str, default):
        if name in self.config:
            return self.config[name]
        try:
            from ceph_tpu.utils.config import global_config
            return global_config().get(name)
        except Exception:
            return default

    def sampling_rate(self) -> float:
        return float(self._get("trace_sampling_rate", 0.0))

    def slow_keep_s(self) -> float:
        return float(self._get("trace_slow_keep_s", 30.0))

    def _buffer_size(self) -> int:
        return int(self._get("trace_buffer_size", 256))

    # -- span creation -----------------------------------------------------
    def start_root(self, name: str,
                   tags: dict | None = None) -> Span | None:
        """Root span for a NEW logical op. Head-sampled roots get a
        propagating trace id; unsampled roots are local-only (tail
        retention candidates); None when tracing is fully off
        (sampling 0 AND tail tracking disabled). While a profiler
        session runs every root is followed, under a capture-only
        id."""
        rate = self.sampling_rate()
        if rate > 0.0 and random.random() < rate:
            return Span(self, name, new_trace_id(), tags=tags)
        if capturing():
            return Span(self, name, new_trace_id() | CAPTURE_ONLY,
                        tags=tags)
        if self.slow_keep_s() > 0.0:
            return Span(self, name, 0, tags=tags)
        return None

    def from_msg(self, name: str, msg,
                 tags: dict | None = None) -> Span | None:
        """Continue a propagated trace from an incoming message's
        appended context; None when the message is untraced."""
        tid = getattr(msg, "trace_id", 0)
        if not tid:
            return None
        return Span(self, name, tid,
                    parent_span_id=getattr(msg, "parent_span_id", 0),
                    tags=tags)

    # -- recording ---------------------------------------------------------
    def record(self, span: Span) -> None:
        tid = span.trace_id
        local = tid == 0 or bool(tid & CAPTURE_ONLY)
        if local and (span.parent_span_id or span.kind == SECTION):
            return                        # nobody sampled it: capture's
        slow = span.duration is not None and \
            0.0 < self.slow_keep_s() <= span.duration
        if local:
            if not slow:
                return                    # unsampled and fast: drop
            # tail retention: promote the local-only root so the mgr
            # can index it (children were never kept — by design)
            span.trace_id = new_trace_id()
            span.tags["tail_sampled"] = True
        if slow:
            span.tags.setdefault("slow", True)
        d = span.dump()
        size = self._buffer_size()
        if size != self._buf.maxlen:      # knob changed at runtime
            self._buf = deque(self._buf, maxlen=size)
        self._buf.append(d)
        if slow:
            self._slow.append(d)
        self._shipq.append(json.dumps(d).encode())

    # -- surfaces ----------------------------------------------------------
    def drain_ship(self, max_n: int = 256) -> list[bytes]:
        """Spans awaiting shipment to the mon (destructive read)."""
        out = []
        while self._shipq and len(out) < max_n:
            out.append(self._shipq.popleft())
        return out

    def ship_pending(self) -> int:
        return len(self._shipq)

    def dump(self) -> dict:
        """The asok ``dump_tracing`` payload."""
        return {
            "service": self.service,
            "sampling_rate": self.sampling_rate(),
            "slow_keep_s": self.slow_keep_s(),
            "buffered": len(self._buf),
            "pending_ship": len(self._shipq),
            "spans": list(self._buf),
            "slow_spans": list(self._slow),
        }


class TraceIndex:
    """Cross-daemon trace reassembly by trace_id (the mgr
    TracingModule's — and the mon's `trace ls/show` — backing store).

    Bounded at ``max_traces`` complete trace groups; the oldest (by
    last span arrival) are evicted first."""

    # spans retained per trace: far above any real op tree (a
    # replicated write is ~10 spans), low enough that one hostile
    # trace_id cannot grow the index without bound
    MAX_SPANS_PER_TRACE = 256
    # tree depth served by show(): beyond it children are elided
    # rather than recursing toward Python's recursion limit
    MAX_TREE_DEPTH = 64

    def __init__(self, max_traces: int = 512):
        self.max_traces = max_traces
        # trace_id -> {"spans": {span_id: span-dict}, "stamp": wall}
        self.traces: "OrderedDict[int, dict]" = OrderedDict()

    def add(self, span: dict) -> None:
        # normalize BEFORE storing: span blobs arrive over the wire
        # from arbitrary clients (MTraceReport is an uncapped
        # fire-and-forget report), and one mistyped field must not
        # poison every later ls()/show() — malformed spans drop here
        try:
            tid = int(span.get("trace_id", 0))
            sid = int(span.get("span_id", 0))
            if not tid or not sid:
                return
            tags = span.get("tags")
            norm = {
                "trace_id": tid,
                "span_id": sid,
                "parent_span_id": int(span.get("parent_span_id", 0)),
                "name": str(span.get("name", "?")),
                "service": str(span.get("service", "?")),
                "kind": str(span.get("kind", INTERVAL)),
                "start": float(span.get("start", 0.0)),
                "duration": float(span.get("duration", 0.0)),
                "tags": tags if isinstance(tags, dict) else {},
            }
        except (TypeError, ValueError):
            return
        ent = self.traces.get(tid)
        if ent is None:
            ent = self.traces[tid] = {"spans": {}, "stamp": 0.0}
        if sid not in ent["spans"] and \
                len(ent["spans"]) >= self.MAX_SPANS_PER_TRACE:
            return                    # one trace can't eat the index
        ent["spans"][sid] = norm
        ent["stamp"] = max(ent["stamp"], norm["start"])
        self.traces.move_to_end(tid)
        while len(self.traces) > self.max_traces:
            self.traces.popitem(last=False)

    # -- views -------------------------------------------------------------
    def _root(self, ent: dict) -> dict | None:
        spans = ent["spans"]
        ids = set(spans)
        roots = [s for s in spans.values()
                 if int(s.get("parent_span_id", 0)) not in ids]
        if not roots:
            return None
        # prefer the true root (no parent at all), else earliest start
        roots.sort(key=lambda s: (int(s.get("parent_span_id", 0)) != 0,
                                  s.get("start", 0.0)))
        return roots[0]

    def duration_of(self, tid: int) -> float:
        ent = self.traces.get(tid)
        if not ent:
            return 0.0
        root = self._root(ent)
        if root is not None and int(root.get("parent_span_id", 0)) == 0:
            return float(root.get("duration", 0.0))
        # partial trace: span envelope
        starts = [s["start"] for s in ent["spans"].values()]
        ends = [s["start"] + s.get("duration", 0.0)
                for s in ent["spans"].values()]
        return max(ends) - min(starts) if starts else 0.0

    def ls(self, limit: int = 20) -> list[dict]:
        """Slowest traces first (ref: the 'where did the latency go'
        entry point)."""
        rows = []
        for tid, ent in self.traces.items():
            root = self._root(ent)
            rows.append({
                "trace_id": tid,
                "root": root.get("name", "?") if root else "?",
                "service": root.get("service", "?") if root else "?",
                "duration": round(self.duration_of(tid), 6),
                "num_spans": len(ent["spans"]),
                "services": sorted({s.get("service", "?")
                                    for s in ent["spans"].values()}),
                "slow": any(s.get("tags", {}).get("slow")
                            for s in ent["spans"].values()),
            })
        rows.sort(key=lambda r: r["duration"], reverse=True)
        return rows[:limit]

    def show(self, tid: int) -> dict | None:
        """One reassembled trace: the span tree plus a per-phase
        latency breakdown (span name -> summed duration)."""
        ent = self.traces.get(tid)
        if ent is None:
            return None
        spans = ent["spans"]
        children: dict[int, list[int]] = {}
        for sid, s in spans.items():
            children.setdefault(
                int(s.get("parent_span_id", 0)), []).append(sid)
        root = self._root(ent)
        t0 = min(s["start"] for s in spans.values())

        def node(sid: int, depth: int = 0) -> dict:
            s = spans[sid]
            kids = sorted(children.get(sid, []),
                          key=lambda c: spans[c]["start"])
            return {
                "span_id": sid,
                "name": s.get("name"),
                "service": s.get("service"),
                "kind": s.get("kind", INTERVAL),
                "offset": round(s["start"] - t0, 6),
                "duration": round(s.get("duration", 0.0), 6),
                "tags": s.get("tags", {}),
                # depth-capped: a hostile parent chain must not drive
                # this recursion toward the interpreter limit
                "children": [node(c, depth + 1) for c in kids]
                if depth < self.MAX_TREE_DEPTH else ([{
                    "span_id": 0, "name": f"({len(kids)} elided)",
                    "service": "", "kind": INTERVAL, "offset": 0.0,
                    "duration": 0.0,
                    "tags": {}, "children": [],
                }] if kids else []),
            }

        phases: dict[str, float] = {}
        for s in spans.values():
            phases[s.get("name", "?")] = round(
                phases.get(s.get("name", "?"), 0.0) +
                s.get("duration", 0.0), 6)
        top = [sid for sid, s in spans.items()
               if int(s.get("parent_span_id", 0)) not in spans]
        return {
            "trace_id": tid,
            "duration": round(self.duration_of(tid), 6),
            "root": root.get("name") if root else None,
            "num_spans": len(spans),
            "phases": phases,
            "tree": [node(sid) for sid in sorted(
                top, key=lambda c: spans[c]["start"])],
        }

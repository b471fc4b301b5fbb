"""The readers of the program's spans on a hand-made span list and a
fake ``ctx``: self time, which ops count, the clock mapping, and
``None`` where records were dropped or nothing was captured."""

import importlib.util
import pathlib
import types

import pytest

from harness import program_spans
from harness.trace_reduce import TraceSummary

BENCH = pathlib.Path(__file__).resolve().parents[1]
MS = 1_000_000
S0 = 50_000 * MS                    # the stretch: 50.000 s .. 51.000 s
S1 = 51_000 * MS                    # on the program's clock
T0 = 7_000_000 * MS                 # the same instant on the trace's
THREAD = 11


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "layer_metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeTracing:
    FIELDS = ("kind", "name", "service", "thread", "t0_ns", "t1_ns",
              "trace_id", "span_id", "parent_span_id")

    def __init__(self, records, dropped=0, cpu_ms=0):
        self.records, self.dropped, self.cpu_ms = records, dropped, cpu_ms

    def captured(self):
        return self.records

    def record_dict(self, rec):
        d = dict(zip(self.FIELDS, rec))
        d["tags"] = dict(zip(rec[9::2], rec[10::2]))
        return d

    def capture_info(self):
        return {"dropped": self.dropped, "records": len(self.records),
                "threads": {THREAD: {
                    "cpu_ns": (0, self.cpu_ms * MS),
                    "clock_ns": (S0, S1)}}}


_ids = iter(range(1000, 10**6))


def sec(name, a_ms, b_ms, trace=0, parent=0, thread=THREAD,
        service="osd.1", **tags):
    return ("section", name, service, thread, S0 + int(a_ms * MS),
            S0 + int(b_ms * MS), trace, next(_ids), parent) \
        + tuple(x for kv in tags.items() for x in kv)


def iv(name, a_ms, b_ms, trace, parent=0, service="osd.1", **tags):
    return ("interval", name, service, 0, S0 + int(a_ms * MS),
            S0 + int(b_ms * MS), trace, next(_ids), parent) \
        + tuple(x for kv in tags.items() for x in kv)


def records():
    """Three client ops: op 1 wholly inside the stretch, op 2 begun
    before it and ended inside, op 3 still running at its end."""
    root1 = iv("client_op", 100, 400, 1, service="client")
    return [
        root1,
        iv("client_op", -50, 300, 2, service="client"),
        iv("client_op", 800, 1200, 3, service="client"),
        iv("osd_op", 110, 390, 1, parent=root1[7], pgid="1.a", osd=1),
        iv("queue", 110, 150, 1, parent=5),
        iv("queue", 10, 90, 2, parent=5),          # op 2: not counted
        iv("ec.agg_wait", 200, 230, 1, parent=5),
        iv("ec_subop_wait", 240, 300, 1, parent=5),
        iv("osd.ec_subread_wait", 300, 310, 1, parent=5),
        # sections of the event loop's thread: fanout holds a commit
        sec("osd.ec_fanout", 240, 260, 1),
        sec("objectstore_commit", 245, 255, 1),
        sec("msg.send", 262, 270, 1),
        sec("msg.send", 500, 504),                 # nobody's op
        sec("ec.launch", 600, 601, 1, service="osd.2"),
        sec("ec.device_wait", 601, 603, 1, service="osd.2"),
        sec("client.submit", 100, 103, 1, service="client"),
        sec("store.read", -5, -1),                 # before the stretch
        sec("msg.recv", 700, 720, thread=12),      # another thread
    ]


def make_ctx(recs, monkeypatch, trace=True, **kw):
    import ceph_tpu.utils as utils
    fake = FakeTracing(recs, **kw)
    monkeypatch.setattr(utils, "tracing", fake, raising=False)
    monkeypatch.setitem(__import__("sys").modules,
                        "ceph_tpu.utils.tracing", fake)
    logs = []
    summary = TraceSummary(
        window_ns=S1 - S0 + 40_000, busy_ns={0: 2 * MS}, ops=[], gaps=[],
        events=2, t0_ns=T0, t1_ns=T0 + (S1 - S0) + 40_000,
        # one device program 1.3 ms after its launch on the trace's
        # clock, one that no launch explains
        intervals={0: [(T0 + 602 * MS + 300_000, T0 + 603 * MS),
                       (T0 + 950 * MS, T0 + 951 * MS)]}) if trace else None
    ctx = types.SimpleNamespace(
        trace=summary, trace_span=(S0 / 1e9, S1 / 1e9),
        obs={"op_lat_s": [0.3, 0.35]}, log=logs.append, logs=logs)
    return ctx


def test_self_time_and_layers(monkeypatch):
    ctx = make_ctx(records(), monkeypatch, cpu_ms=100)
    red = program_spans.reduce(ctx)
    # ops 1 and 2 ended inside the stretch, op 1 lies wholly inside
    assert (red.ops_ended, red.ops_inside) == (2, 1)
    # fanout 20 ms holds a 10 ms commit: 10 self; the other thread's
    # and the early section do not count
    assert red.self_ns == {"osd": 10 * MS, "store": 10 * MS,
                           "msg": 12 * MS, "ec": 3 * MS,
                           "client": 3 * MS}
    assert red.sections["osd.ec_fanout"]["total"] == 20 * MS
    assert red.sections["osd.ec_fanout"]["self"] == 10 * MS
    assert red.sections["msg.send"]["count"] == 2
    assert "store.read" not in red.sections
    assert "msg.recv" not in red.sections
    op_host = reader("op_host_ms")
    assert op_host.read(ctx, "osd") == pytest.approx(5.0)     # 10 / 2
    assert op_host.read(ctx, "msg") == pytest.approx(6.0)
    assert op_host.read(ctx, "store") == pytest.approx(5.0)
    assert op_host.read(ctx, "ec") == pytest.approx(1.5)
    assert op_host.read(ctx, "client") == pytest.approx(1.5)
    # 100 ms of the thread's CPU less 38 ms of sections, over 2 ops
    assert op_host.read(ctx, "unspanned") == pytest.approx(31.0)
    assert sum(op_host.read(ctx, v) for v in
               ("client", "msg", "osd", "ec", "store", "unspanned")) \
        * red.ops_ended == pytest.approx(100.0)
    assert ctx._program_spans is red            # reduced once


def test_straddling_sections_are_cut_not_double_counted():
    a = dict(name="a", t0_ns=0, t1_ns=10)
    b = dict(name="b", t0_ns=5, t1_ns=15)           # leaves a late
    c = dict(name="c", t0_ns=20, t1_ns=30)
    d = dict(name="d", t0_ns=22, t1_ns=24)
    segs = [(s, e, sec["name"])
            for s, e, sec in program_spans.self_segments([c, a, d, b])]
    assert segs == [(0, 5, "a"), (5, 10, "b"), (20, 22, "c"),
                    (22, 24, "d"), (24, 30, "c")]


def test_waits_are_medians_over_ops_wholly_inside(monkeypatch):
    recs = records()
    root4 = iv("client_op", 500, 700, 4, service="client")
    recs += [root4, iv("queue", 510, 530, 4, parent=5),
             iv("queue", 540, 560, 4, parent=5)]    # a resend: added up
    ctx = make_ctx(recs, monkeypatch)
    op_wait = reader("op_wait_ms")
    # op 1 waited 40, op 4 20 + 20; op 2 (begun early) is left out
    assert op_wait.read(ctx, "queue") == pytest.approx(40.0)
    assert sorted(program_spans.reduce(ctx).waits_ms["queue"]) == \
        [40.0, 40.0]
    assert op_wait.read(ctx, "agg") == pytest.approx(30.0)
    # the sub-writes' wait and the sub-reads' are one stage
    assert op_wait.read(ctx, "subop") == pytest.approx(70.0)
    assert program_spans.reduce(ctx).root_p50_ms == pytest.approx(200.0)


def test_clock_mapping_check_and_idle_attribution(monkeypatch):
    ctx = make_ctx(records(), monkeypatch)
    red = program_spans.reduce(ctx)
    # the trace's stretch is 40 us longer than the host's
    assert red.skew_ns == 40_000
    # the first busy interval lies within 2 ms of the launch..wait
    # pair once the pair is on the trace's clock; the second does not
    assert red.clock_check == pytest.approx(0.5)
    idle = dict(red.idle_by)
    # every section lies in a gap here but for the 0.7 ms of
    # ec.device_wait under the first program; no section: the rest
    assert idle["msg.send"] == pytest.approx(0.012)
    assert idle["osd.ec_fanout"] == pytest.approx(0.010)
    assert idle["ec.device_wait"] == pytest.approx(0.0013)
    busy = 0.0017
    assert sum(idle.values()) == pytest.approx(
        ctx.trace.window_ns / 1e9 - busy)
    assert any("clock check: 50.0%" in ln for ln in ctx.logs)
    assert any("+0.040 ms" in ln for ln in ctx.logs)
    assert any("spans interval queue by pg" in ln and "1.a:1/40.0" in ln
               for ln in ctx.logs)
    # op 1: 10 ms to the primary's admission, 280 in osd_op, 10 back
    assert any("op stages" in ln and "to_osd=10.0/10.0 osd_op=280.0/280.0"
               " reply=10.0/10.0" in ln for ln in ctx.logs)


def test_none_when_dropped_or_empty_or_without_a_capture(monkeypatch):
    op_host, op_wait = reader("op_host_ms"), reader("op_wait_ms")
    ctx = make_ctx(records(), monkeypatch, dropped=3)
    assert op_host.read(ctx, "msg") is None
    assert op_wait.read(ctx, "queue") is None
    assert any("dropped 3" in ln for ln in ctx.logs)
    ctx = make_ctx([], monkeypatch)
    assert op_host.read(ctx, "msg") is None
    # a stage no op passed: nothing to read, not 0
    ctx = make_ctx([r for r in records() if r[1] != "ec.agg_wait"],
                   monkeypatch)
    assert op_wait.read(ctx, "agg") is None
    assert op_host.read(ctx, "ec") == pytest.approx(1.5)
    # a program from before the capture (the parent commit)
    old = types.SimpleNamespace()
    monkeypatch.setitem(__import__("sys").modules,
                        "ceph_tpu.utils.tracing", old)
    import ceph_tpu.utils as utils
    monkeypatch.setattr(utils, "tracing", old, raising=False)
    ctx = make_ctx(records(), monkeypatch)
    monkeypatch.setitem(__import__("sys").modules,
                        "ceph_tpu.utils.tracing", old)
    monkeypatch.setattr(utils, "tracing", old, raising=False)
    assert op_host.read(ctx, "msg") is None
    # without a device trace (a CPU rehearsal) the spans still reduce
    ctx = make_ctx(records(), monkeypatch, trace=False)
    assert op_host.read(ctx, "msg") == pytest.approx(6.0)
    assert program_spans.reduce(ctx).clock_check is None


def test_osd_commit_ms_adds_up_over_the_osds():
    def osd(n, s):
        return types.SimpleNamespace(perf=types.SimpleNamespace(
            dump=lambda: {"commit_latency": {"avgcount": n, "sum": s}}))
    read = reader("osd_commit_ms").read
    ctx = types.SimpleNamespace(osds=[osd(3, 0.09), osd(1, 0.03),
                                      osd(0, 0.0)])
    assert read(ctx) == pytest.approx(30.0)
    assert read(types.SimpleNamespace(osds=[osd(0, 0.0)])) is None
    assert read(types.SimpleNamespace(osds=[])) is None


def test_xla_compiles_counts_uncached_compiles_in_the_window(monkeypatch):
    from ceph_tpu.utils import devmon
    sec_ns = 1_000_000_000
    events = [
        (5 * sec_ns, "jit(_fused)", 1.5, False, "ec_encode_crc"),  # set-up
        (12 * sec_ns, "jit(_fused)", 0.2, False, "ec_encode_crc"),
        (13 * sec_ns, "jit(decode)", 0.1, True, "ec_decode"),     # cached
        (14 * sec_ns, "jit(_run)", 30.0, False, "crush_map_pgs"),
        (15 * sec_ns, "jit(squeeze)", 0.01, False, "other"),
        (25 * sec_ns, "jit(_fused)", 0.2, False, "ec_encode_crc"),  # after
    ]
    monkeypatch.setattr(devmon, "compile_events", lambda: events)
    logs = []
    ctx = types.SimpleNamespace(t_start=2.0, setup_s=8.0, window_s=10.0,
                                log=logs.append)
    read = reader("xla_compiles").read
    assert read(ctx, "ec") == 2            # its own and the nameless one
    assert read(ctx, "crush") == 2
    assert any("2 outside it" in ln for ln in logs)
    ctx.window_s = None                    # the window never closed
    assert read(ctx, "ec") is None

"""``sweep_idle_ms`` on hand-made sweeps: the device's clock offset
recovered from the program's sync points, idle put down to the stage
that was open, the sum identity with ``device_idle_pct``, and ``None``
where the clocks cannot be reconciled, nothing was captured or the tree
has no ``crush.sweep``."""

import sys
import types

import pytest

from harness.trace_reduce import TraceSummary
from test_program_spans import (FakeTracing, MS, S0, S1, T0, reader,
                                sec)

# one sweep of a 50 ms period on the host's clock (ms from its start):
# the prelude's jnp.zeros at 0.5, the block 1.2 .. 39.8 in three
# operations 2 us apart, the counts' slice 41.0 .. 41.2
PERIOD = [("crush.test", 0.0, 49.0), ("crush.sweep", 0.1, 42.0),
          ("crush.dispatch", 1.0, 1.5), ("crush.force", 1.6, 40.0),
          ("crush.readback", 42.5, 46.0)]
BUSY = [(0.5, 0.502), (1.2, 10.0), (10.002, 20.0), (20.002, 39.8),
        (41.0, 41.2)]
N = 20                                  # sweeps in the 1,000 ms stretch
# what each stage holds of a period's 11.202 ms of idle: test 0..0.1,
# 42..42.5, 46..49; sweep 0.1..0.5, 0.502..1.0, 40..41, 41.2..42;
# dispatch 1.0..1.2; force the block's two gaps, 39.8..40; readback
# 42.5..46; other (the driver) 49..50
SPLIT = {"test": 3.6, "sweep": 2.698, "dispatch": 0.2, "force": 0.204,
         "readback": 3.5, "other": 1.0}


def make_ctx(monkeypatch, offsets=(1.3,) * N, names=None, trace=True):
    """A traced stretch of N sweeps whose device clock runs
    ``offsets[k]`` ms ahead of the host's in sweep k."""
    import ceph_tpu.utils as utils
    recs, busy, host = [], [], [("bench.stretch", T0, T0 + S1 - S0)]
    for k, off in enumerate(offsets):
        at = 50.0 * k
        recs += [sec(names.get(n, n) if names else n, at + a, at + b,
                     service="crush") for n, a, b in PERIOD]
        busy += [(T0 + int((at + a + off) * MS), T0 + int((at + b + off) * MS))
                 for a, b in BUSY]
        host.append(("bench.sweep", T0 + int(at * MS),
                     T0 + int((at + 49.2) * MS)))
    busy = [(max(a, T0), min(b, T0 + S1 - S0)) for a, b in busy
            if b > T0 and a < T0 + S1 - S0]       # the trace's clipping
    fake = FakeTracing(recs, cpu_ms=900)
    monkeypatch.setattr(utils, "tracing", fake, raising=False)
    monkeypatch.setitem(sys.modules, "ceph_tpu.utils.tracing", fake)
    summary = TraceSummary(
        window_ns=S1 - S0, busy_ns={0: sum(b - a for a, b in busy)},
        ops=[], gaps=[], events=len(busy), t0_ns=T0, t1_ns=T0 + S1 - S0,
        intervals={0: busy}, host_spans=host) if trace else None
    logs = []
    return types.SimpleNamespace(
        trace=summary, trace_span=(S0 / 1e9, S1 / 1e9), obs={},
        log=logs.append, logs=logs)


def split_of(ctx):
    return {v: reader("sweep_idle_ms").read(ctx, v) for v in SPLIT}


def bracket_of(ctx):
    line = next(m for m in ctx.logs if "by d in [" in m)
    lo, hi = line.partition("by d in [")[2].partition("]")[0].split(", ")
    return float(lo), float(hi)


@pytest.mark.parametrize("offset", [-1.3, -0.4, 1.3, 2.6])
def test_a_planted_offset_is_recovered(monkeypatch, offset):
    ctx = make_ctx(monkeypatch, (offset,) * N)
    split = split_of(ctx)
    lo, hi = bracket_of(ctx)
    # the force ends 0.2 ms after the block, the block starts 0.2 ms
    # into its dispatch; the zeros and the slice, which a wrong estimate
    # puts in the next epoch or the last, bound nothing
    assert (lo, hi) == pytest.approx((offset - 0.2, offset + 0.2))
    assert abs((lo + hi) / 2 - offset) < 0.2
    idle_s = (ctx.trace.window_ns - ctx.trace.busy_s("max") * 1e9) / 1e9
    assert sum(split.values()) * N / 1e3 == pytest.approx(idle_s, rel=1e-9)


def test_idle_falls_under_the_stage_that_was_open(monkeypatch):
    split = split_of(make_ctx(monkeypatch, (-1.3,) * N))
    # the device's stamps run 1.3 ms behind: its window is the host's
    # 1.3 .. 1,001.3 ms, so the first sweep's idle before its block
    # (test 0.1, sweep 0.898, dispatch 0.2) lies before it, and 1.3 ms
    # after the last sweep's end (other) inside it
    want = dict(SPLIT, test=SPLIT["test"] - 0.1 / N,
                sweep=SPLIT["sweep"] - 0.898 / N,
                dispatch=SPLIT["dispatch"] - 0.2 / N,
                other=SPLIT["other"] + 1.3 / N)
    assert split == pytest.approx(want, abs=1e-6)


def test_the_burst_rule(monkeypatch):
    blocks = reader("sweep_idle_ms").bursts(
        [(0, 10), (10_002, 20_000), (80_000, 90_000)])
    assert blocks == [[0, 20_000], [80_000, 90_000]]


def test_clocks_that_cannot_be_reconciled_read_none(monkeypatch):
    # the offset jumps by 1 ms halfway: no one offset fits both halves
    ctx = make_ctx(monkeypatch, (-1.3,) * 10 + (-0.3,) * 10)
    assert reader("sweep_idle_ms").read(ctx, "sweep") is None
    assert any("cannot be reconciled" in m for m in ctx.logs)


def test_no_capture_no_trace_or_no_sweep_section_reads_none(monkeypatch):
    empty = make_ctx(monkeypatch)
    sys.modules["ceph_tpu.utils.tracing"].records = []
    assert reader("sweep_idle_ms").read(empty, "test") is None
    assert reader("sweep_idle_ms").read(
        make_ctx(monkeypatch, trace=False), "test") is None
    # a parent's tree: its indep sweep recorded crush.indep_block only
    parent = make_ctx(monkeypatch, names={
        "crush.sweep": "crush.indep_block", "crush.test": "bench.x",
        "crush.dispatch": "bench.y", "crush.force": "bench.z",
        "crush.readback": "bench.w"})
    assert all(v is None for v in split_of(parent).values())
    assert any("no crush.sweep" in m for m in parent.logs)

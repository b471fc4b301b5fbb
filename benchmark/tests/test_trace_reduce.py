"""The trace reduction on a small trace recorded on a v5e
(``record_trace.py``): three EC encode launches of about 69 us each,
20 ms of sleep after each, a ``bench.encode`` span around each and a
``bench.stretch`` span around all of it. In that file the device's
clock runs about 1.3 ms ahead of the host's, so the first launch lies
before the stretch's opening: two launches are inside it."""

import pathlib

import pytest

from harness import trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def whole():
    return trace_reduce.reduce_trace(str(TRACE))


@pytest.fixture(scope="module")
def stretch():
    return trace_reduce.reduce_trace(str(TRACE), span_name="bench.stretch")


def test_whole_trace_has_three_launches(whole):
    assert list(whole.busy_ns) == [0]
    # modules of 68,964 + 68,958 + 69,196 ns, their ops a little less
    assert 195_000 < whole.busy_ns[0] < 207_200
    assert whole.events == 21            # 7 operations a launch
    # from the first op's start to the last op's end
    assert 42_900_000 < whole.window_ns < 42_950_000


def test_stretch_is_the_host_span_and_holds_two_launches(stretch):
    assert stretch.window_ns == 64_323_009
    assert stretch.events == 14
    assert 130_000 < stretch.busy_ns[0] < 138_200
    idle = 1 - stretch.busy_ns[0] / stretch.window_ns
    assert 0.9978 < idle < 0.9980


def test_the_fusion_takes_most_of_the_device_time(stretch):
    name, seconds = stretch.ops[0]
    assert name == "fusion.13 u8[3,524288] fusion kOutput"
    assert 2 * 59_000e-9 < seconds < 2 * 60_500e-9
    assert len(stretch.ops) <= 10


@pytest.mark.parametrize("text,name", [
    ("%fusion.3 = u8[8,64]{1,0:T(8,128)(4,1)S(1)} fusion(u8[8]{0} %p), "
     "kind=kLoop, calls=%fc.1", "fusion.3 u8[8,64] fusion kLoop"),
    ("%cs = (s8[24,64]{1,0}, u32[]{:S(2)}) copy-start(s8[24,64]{1,0} %b), "
     "cross_program_prefetch_index=0", "cs (s8[24,64],..) copy-start"),
    ("jit_encode_stripes(16624281316594757718)",
     "jit_encode_stripes(16624281316594757718)"),
])
def test_an_operation_is_named_by_what_it_makes_and_is(text, name):
    assert trace_reduce._op_name(text) == name


def test_gaps_are_named_by_the_innermost_host_span(stretch):
    gaps = dict(stretch.gaps)
    # the sleeps lie outside the bench.encode spans
    assert gaps["outside_bench_spans"] > 0.060
    assert gaps.get("bench.encode", 0) < 0.003
    assert abs(sum(gaps.values()) + stretch.busy_ns[0] / 1e9
               - stretch.window_ns / 1e9) < 1e-9


def test_busy_within_a_host_span(stretch):
    spans = stretch.spans_named("bench.encode")
    assert len(spans) == 3
    # clock skew: a launch ends before the span that made it opens, so
    # the spans hold none of the busy time in this file
    assert stretch.busy_within(spans) < 70e-6
    assert stretch.busy_within([(stretch.t0_ns, stretch.t1_ns)]) == \
        pytest.approx(stretch.busy_ns[0] / 1e9)


def test_self_times_take_nested_events_out():
    evs = [("while", 0, 100), ("body.a", 10, 40), ("body.b", 50, 90),
           ("after", 120, 130)]
    assert dict(trace_reduce._self_times(evs)) == {
        "while": 30, "body.a": 30, "body.b": 40, "after": 10}


def test_union_and_clip():
    merged = trace_reduce._union([(5, 9), (0, 3), (2, 4), (9, 12)])
    assert merged == [[0, 4], [5, 12]]
    assert trace_reduce._clip(merged, 3, 10) == [(3, 4), (5, 10)]


def test_no_device_plane_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.reduce_trace(str(tmp_path))

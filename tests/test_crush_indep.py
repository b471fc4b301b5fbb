"""The erasure rule upstream makes (``set_chooseleaf_tries 5``,
``set_choose_tries 100``, ``take``, ``chooseleaf indep 0 type host``,
``emit``): the rule VM against ``crush/mapper_ref.py`` AND against the
benchmark's plain reference, position by position; bad mappings counted
and printed as upstream's CrushTester does; the builder and the mon
making that rule; the indep counters against what the scalar spec
counts; the block that finishes its later rounds an eighth as wide, with
the floor of that patched down to a CPU's sizes."""

import asyncio
import pathlib
import sys

import jax
import numpy as np
import pytest

from ceph_tpu.bench import crushtool
from ceph_tpu.crush import builder, mapper as mapper_mod, mapper_ref
from ceph_tpu.crush.compiler import compile_crushmap, decompile_crushmap
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.tester import CrushTester
from ceph_tpu.crush.types import (ITEM_NONE, OP_CHOOSELEAF_INDEP,
                                  OP_CHOOSE_INDEP, OP_EMIT,
                                  OP_SET_CHOOSELEAF_TRIES,
                                  OP_SET_CHOOSE_TRIES, OP_TAKE, WEIGHT_ONE)

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from reference import crush_indep_ref, crush_ref        # noqa: E402

DOCS_RULE = ("\tid {id}\n\ttype erasure\n\tstep set_chooseleaf_tries 5\n"
             "\tstep set_choose_tries 100\n\tstep take {root}\n"
             "\tstep chooseleaf indep 0 type host\n\tstep emit\n}}\n")

# three-level maps (racks x hosts x OSDs): name -> (hosts, OSDs a host,
# racks, devices weighted out, hosts that can still take a shard)
CASES = {
    # every device in
    "in": (12, 4, 4, (), 12),
    # devices weighted out: all of hosts 0 and 1 (10 hosts left, so 11
    # positions leave a hole) and one OSD of host 2 (the leaf retries
    # inside it: set_chooseleaf_tries 5 matters)
    "out": (12, 4, 4, (0, 1, 2, 3, 4, 5, 6, 7, 8), 10),
    # fewer hosts than positions: every mapping has a hole
    "few": (4, 3, 2, (), 4),
}
# maps only the narrowing tests use (they would slow the others)
WIDE_CASES = {
    # hosts far over the width: a round leaves few lanes unfilled
    "wide": (96, 2, 8, (), 96),
    # one of the two OSDs out in every other host: the leaf retries
    "wide-out": (96, 2, 8, tuple(range(0, 192, 4)), 96),
}


def _maps(case):
    """(program's map, its erasure rule id, weights, reference's map
    and steps) of a case."""
    hosts, per, racks, out, _usable = {**CASES, **WIDE_CASES}[case]
    m, root = builder.build_hierarchy(hosts, per, n_racks=racks)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
    w = np.full(m.max_devices, WEIGHT_ONE, dtype=np.int64)
    w[list(out)] = 0
    rm = crush_ref.build_map({"osds": hosts * per, "hosts": hosts,
                              "racks": racks, "failure_domain": "host"})
    assert set(rm.buckets) == set(m.buckets)
    for bid, rb in rm.buckets.items():
        assert list(m.buckets[bid].items) == rb.items
        assert list(m.buckets[bid].weights) == rb.weights
    steps = crush_indep_ref.parse_rule(
        DOCS_RULE.format(id=rid, root="root"), rm)
    return m, rid, w, rm, steps


@pytest.mark.parametrize("width", [6, 11])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rule_vm_is_both_references_position_by_position(case, width):
    m, rid, w, rm, steps = _maps(case)
    assert [(s.op, s.arg1, s.arg2) for s in m.rules[rid].steps] == \
        crush_indep_ref.step_codes(steps)
    xs = np.arange(5000, 5000 + (96 if case == "few" else 384),
                   dtype=np.uint32)
    got = np.asarray(Mapper(m, w, block=128).map_pgs(rid, xs, width))
    spec = np.array([mapper_ref.do_rule(m, rid, int(x), width, w.tolist())
                     for x in xs])
    plain = crush_indep_ref.map_batch(rm, steps, xs, width, w.tolist())
    assert np.array_equal(got, spec)
    assert np.array_equal(got, plain)
    holes = int((got == ITEM_NONE).sum())
    usable = CASES[case][4]             # one hole a missing host, no more
    assert holes == max(0, width - usable) * len(xs)
    # one host a position: no host holds two shards
    per = CASES[case][1]
    for row in got:
        live = row[row != ITEM_NONE] // per
        assert len(set(live.tolist())) == len(live)


def test_set_chooseleaf_tries_matters_once_a_device_is_out():
    """With one OSD of a host out, the short form (one leaf try) gives
    the host up for the round where upstream's rule tries another of
    its OSDs; with every device in the steps change nothing."""
    m, rid, w, _rm, _steps = _maps("out")
    root = m.rules[rid].steps[2].arg1
    short = builder.add_multistep_rule(
        m, root, [builder.RuleStep(OP_CHOOSELEAF_INDEP, 0, builder.TYPE_HOST)],
        indep=True)
    xs = range(2000, 2300)
    full = [WEIGHT_ONE] * m.max_devices
    assert all(mapper_ref.do_rule(m, rid, x, 6, full)
               == mapper_ref.do_rule(m, short, x, 6, full) for x in xs)
    assert any(mapper_ref.do_rule(m, rid, x, 6, w.tolist())
               != mapper_ref.do_rule(m, short, x, 6, w.tolist()) for x in xs)
    got = np.asarray(Mapper(m, w, block=128).map_pgs(
        short, np.arange(2000, 2300, dtype=np.uint32), 6))
    assert got.tolist() == [mapper_ref.do_rule(m, short, x, 6, w.tolist())
                            for x in xs]


@pytest.mark.parametrize("keep", [False, True], ids=["sweep", "keep"])
@pytest.mark.parametrize("case,width", [("few", 6), ("out", 11), ("in", 11)])
def test_tester_counts_a_holed_mapping_as_bad(case, width, keep):
    m, rid, w, _rm, _steps = _maps(case)
    n = 64
    tester = CrushTester(m, w, batch=64)
    res = tester.test(rid, width, 100, 100 + n - 1, keep_mappings=keep)
    spec = [mapper_ref.do_rule(m, rid, x, width, w.tolist())
            for x in range(100, 100 + n)]
    want = [(x, row) for x, row in zip(range(100, 100 + n), spec)
            if len(row) != width or ITEM_NONE in row]
    assert res.bad_mappings == len(want)
    assert res.path == "xla" and res.indep
    assert (len(want) == n) == (case != "in")
    counts = np.bincount([d for row in spec for d in row if d != ITEM_NONE],
                         minlength=m.max_devices)
    assert np.array_equal(res.device_counts, counts)
    # upstream's line, holes printed as CRUSH_ITEM_NONE
    assert tester.bad_mapping_lines(res) == [
        f"bad mapping rule {rid} x {x} num_rep {width} result "
        f"[{','.join(map(str, row))}]" for x, row in want]
    if want:
        assert "2147483647" in tester.bad_mapping_lines(res)[0]


@pytest.mark.parametrize("show_mappings", [False, True],
                         ids=["sweep", "keep"])
def test_crushtool_prints_upstreams_bad_mapping_line(show_mappings, capsys):
    argv = ["--build", "--num-osds", "12", "--hosts", "4", "--indep",
            "--test", "--rule", "0", "--num-rep", "6", "--min-x", "1",
            "--max-x", "16", "--show-bad-mappings", "--show-statistics",
            "--batch", "16"]
    out = crushtool.main(argv + (["--show-mappings"] if show_mappings
                                 else []))
    printed = capsys.readouterr().out.splitlines()
    bad = [l for l in printed if l.startswith("bad mapping rule 0 x ")]
    assert out["bad_mappings"] == 16 and len(bad) == 16
    assert out["mapping_path"] == "xla"
    m = crushtool.build_map(crushtool.parse_args(argv))
    row = mapper_ref.do_rule(m, 0, 1, 6)
    assert row.count(ITEM_NONE) == 2
    assert bad[0] == ("bad mapping rule 0 x 1 num_rep 6 result ["
                      + ",".join(map(str, row)) + "]")
    assert any(l.startswith("total mappings 16 ") and
               l.endswith("on path xla") for l in printed)


def test_a_short_firstn_result_is_still_bad_and_prints_what_it_got(capsys):
    out = crushtool.main(["--build", "--num-osds", "6", "--hosts", "3",
                          "--test", "--num-rep", "5", "--max-x", "7",
                          "--show-bad-mappings", "--json", "--batch", "8"])
    printed = capsys.readouterr().out.splitlines()
    bad = [l for l in printed if l.startswith("bad mapping rule 0 x ")]
    assert out["bad_mappings"] == 8 and len(bad) == 8
    assert "2147483647" not in "".join(bad)
    assert all(l.split("result ")[1].count(",") == 2 for l in bad)
    assert '"mapping_path"' in printed[-1]


@pytest.mark.parametrize("domain,op", [(builder.TYPE_HOST, OP_CHOOSELEAF_INDEP),
                                       (builder.TYPE_OSD, OP_CHOOSE_INDEP)],
                         ids=["host", "osd"])
def test_add_simple_rule_makes_upstreams_erasure_rule(domain, op):
    m, root = builder.build_hierarchy(4, 2)
    builder.add_simple_rule(m, root, builder.TYPE_HOST)
    rid = builder.add_simple_rule(m, root, domain, name="ecpool", indep=True)
    r = m.rules[rid]
    assert r.type == 3
    assert [(s.op, s.arg1, s.arg2) for s in r.steps] == [
        (OP_SET_CHOOSELEAF_TRIES, 5, 0), (OP_SET_CHOOSE_TRIES, 100, 0),
        (OP_TAKE, root, 0), (op, 0, domain), (OP_EMIT, 0, 0)]
    # the replicated rule keeps its three steps
    assert [s.op for s in m.rules[0].steps][0] == OP_TAKE
    text = decompile_crushmap(m)
    want = "rule ecpool {\n" + DOCS_RULE.format(id=rid, root="root")
    if domain == builder.TYPE_OSD:
        want = want.replace("chooseleaf indep 0 type host",
                            "choose indep 0 type osd")
    assert want in text
    again = compile_crushmap(text)
    assert [(s.op, s.arg1, s.arg2) for s in again.rules[rid].steps] == \
        [(s.op, s.arg1, s.arg2) for s in r.steps]


def test_crushtool_build_indep_makes_it_too():
    m = crushtool.build_map(crushtool.parse_args(
        ["--build", "--num-osds", "8", "--hosts", "4", "--indep"]))
    assert ("rule rule0 {\n" + DOCS_RULE.format(id=0, root="root")
            in decompile_crushmap(m))


def test_an_erasure_pool_made_by_the_mon_carries_the_set_steps():
    from ceph_tpu.mon import MonClient
    from tests.test_mon import (run, start_mons, stop_all, wait_for,
                                wait_quorum)

    async def go():
        mons, monmap = await start_mons(1)
        leader = await wait_quorum(mons)
        await wait_for(lambda: leader.osdmon.osdmap is not None,
                       msg="osdmap")
        mc = MonClient("client.admin", monmap)
        for i in range(3):
            ret, _, _ = await mc.command({"prefix": "osd new"})
            assert ret == 0
            ret, rs, _ = await mc.command(
                {"prefix": "osd crush add", "id": i, "weight": 1.0,
                 "host": f"host{i}"})
            assert ret == 0, rs
        ret, rs, _ = await mc.command(
            {"prefix": "osd erasure-code-profile set", "name": "p21",
             "profile": ["k=2", "m=1"]})
        assert ret == 0, rs
        ret, rs, _ = await mc.command(
            {"prefix": "osd pool create", "pool": "ecpool", "pg_num": 8,
             "pool_type": "erasure", "erasure_code_profile": "p21"})
        assert ret == 0, rs
        om = leader.osdmon.osdmap
        pool = next(p for p in om.pools.values() if p.name == "ecpool")
        text = decompile_crushmap(om.crush)
        await stop_all(mons, [mc])
        made.append((pool.crush_rule, text))

    made = []
    run(go())
    (rid, text), = made
    root = text.split("step take ")[1].split("\n")[0]
    assert ("rule ec_p21 {\n" + DOCS_RULE.format(id=rid, root=root)) in text


def _spec_rounds(m, rid, w, xs, width):
    """(rounds of every input's one choose_indep, holes, the vectors)
    by the scalar spec."""
    rounds, rows = [], []
    for x in xs:
        rows.append(mapper_ref.do_rule(m, rid, int(x), width, w.tolist(),
                                       indep_rounds=rounds))
    assert len(rounds) == len(rows)
    return rounds, sum(r.count(ITEM_NONE) for r in rows), rows


def _widths_run(rounds, n, tries=100):
    """The width of every round a block of n lanes runs, from the
    scalar spec's rounds: after round k the inputs that took more than
    k rounds are unfilled, and the block goes on in the next of
    ``narrow_widths(n)`` once no more lanes than that are."""
    caps = list(mapper_mod.narrow_widths(n))
    widths, width, left = [], n, n
    while len(widths) < tries and left > 0:
        while caps and left <= caps[0]:
            width = caps.pop(0)
        widths.append(width)
        left = sum(r > len(widths) for r in rounds)
    return widths


@pytest.fixture
def narrow_from(monkeypatch):
    """Sets ``MIN_NARROW_WIDTH``. The rule VM's programs are cached by
    rule and not by the floor: the caches are emptied round the patch."""
    def clear():
        for cached in (mapper_mod._rule_body, mapper_mod._compiled_rule,
                       mapper_mod._compiled_sweep):
            cached.cache_clear()

    def patch(floor):
        clear()
        monkeypatch.setattr(mapper_mod, "MIN_NARROW_WIDTH", floor)
    yield patch
    clear()


@pytest.mark.parametrize("case,width,n,floor", [
    ("in", 11, 512, None), ("in", 6, 512, None), ("out", 11, 256, None),
    ("out", 6, 256, None), ("few", 6, 32, None),
    # narrowing: after round 1; after five rounds, hosts and an OSD out
    ("wide", 4, 128, 128), ("out", 6, 128, 128)])
def test_indep_counters_are_what_the_scalar_spec_counts(case, width, n,
                                                        floor, narrow_from):
    """One block of n lanes: ``indep_rounds`` is its unluckiest
    input's rounds, ``indep_lane_rounds_needed`` the sum of every
    input's, ``indep_lane_rounds_run`` the widths of its rounds,
    ``indep_holes`` the ITEM_NONEs emitted."""
    if floor:
        narrow_from(floor)
    m, rid, w, _rm, _steps = _maps(case)
    rounds, holes, _rows = _spec_rounds(m, rid, w, range(1, n + 1), width)
    widths = _widths_run(rounds, n)
    narrowed = widths[-1] < n
    assert narrowed == bool(floor)
    before = mapper_mod.PERF.dump()
    res = CrushTester(m, w, batch=n).test(rid, width, 1, n)
    after = mapper_mod.PERF.dump()
    delta = {k: after[k] - before[k] for k in mapper_mod.INDEP_TALLY}
    assert delta == {"indep_blocks": 1, "indep_rounds": max(rounds),
                     "indep_lane_rounds_needed": sum(rounds),
                     "indep_lane_rounds_run": sum(widths),
                     "indep_blocks_narrowed": int(narrowed),
                     "indep_holes": holes}
    assert after["sweep_lanes"] - before["sweep_lanes"] == n
    assert (res.bad_mappings > 0) == (holes > 0)
    if case == "few":
        assert max(rounds) == 100       # set_choose_tries, not the map's 50


def test_a_firstn_sweep_moves_no_indep_counter():
    m, root = builder.build_hierarchy(6, 2)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
    before = mapper_mod.PERF.dump()
    res = CrushTester(m, batch=64).test(rid, 3, 0, 63)
    after = mapper_mod.PERF.dump()
    assert res.bad_mappings == 0 and not res.indep
    assert all(after[k] == before[k] for k in mapper_mod.INDEP_TALLY)


def test_the_indep_sweep_is_a_tracing_section(monkeypatch):
    from ceph_tpu.utils import tracing
    m, rid, w, _rm, _steps = _maps("in")
    made = []

    def section(name, ctx=None, tracer=None, service=""):
        # a Span as a profiler session would have it: its tags are kept
        made.append(tracing.Span(tracer, name, 0, 0, None, tracing.SECTION,
                                 service))
        return made[-1]
    monkeypatch.setattr(mapper_mod.tracing, "section", section)

    def sweep_tags():
        found = [s.tags for s in made if s.name == "crush.sweep"]
        made.clear()
        return found
    CrushTester(m, w, batch=64).test(rid, 6, 0, 63)
    assert sweep_tags() == [{"lanes": 64, "takes": 1, "blocks": 1,
                             "width": 64, "narrow_width": 0}]
    assert mapper_mod.narrow_widths(1 << 20) == (1 << 17, 1 << 13)
    root = m.rules[rid].steps[2].arg1
    firstn = builder.add_simple_rule(m, root, builder.TYPE_HOST)
    CrushTester(m, w, batch=64).test(firstn, 3, 0, 63)
    assert sweep_tags() == [{"lanes": 64, "takes": 1, "blocks": 1,
                             "width": 64}]


def _block_program(m, w, rid, width, n):
    """The rule VM's program for a block of n lanes, with its tally:
    ``(jitted run(arrays, xs) -> (mappings, stats), arrays)``."""
    mp = Mapper(m, w, block=n)
    return jax.jit(mapper_mod._rule_body(*mp._rule_key(rid, width),
                                         tally=mapper_mod.INDEP_TALLY)), \
        mp.arrays


# case, width, the widths of a 128-lane block's rounds (it narrows to 16
# lanes, then to 1)
NARROWING = {
    "after-round-1": ("wide", 4, [128, 16]),
    # exactly 16 lanes are left after round 1, and one after round 2;
    # the leaf retries (set_chooseleaf_tries 5) run inside narrow rounds
    "osd-out-leaf-retries": ("wide-out", 4, [128, 16, 1]),
    # over an eighth unfilled after round 1: full width again, then narrow
    "after-round-4": ("in", 6, [128] * 4 + [16] * 2),
    "hosts-out-after-round-5": ("out", 6, [128] * 5 + [16] * 4 + [1]),
    "eleven-wide": ("wide", 11, [128] * 2 + [16] * 2 + [1]),
    # hosts < width: every lane keeps its holes in place, the loop runs
    # to set_choose_tries at full width
    "holes-never": ("few", 6, [128] * 100),
}


@pytest.mark.parametrize("name", sorted(NARROWING))
def test_a_narrowed_block_is_both_references_position_by_position(
        name, narrow_from):
    case, width, widths = NARROWING[name]
    n = 128
    narrow_from(n)
    assert mapper_mod.narrow_widths(n) == (16, 1)
    m, rid, w, rm, steps = _maps(case)
    xs = np.arange(1, 1 + n, dtype=np.uint32)
    rounds, holes, spec = _spec_rounds(m, rid, w, xs, width)
    # the map does what the case is named for
    assert _widths_run(rounds, n) == widths and len(widths) == max(rounds)
    fn, arrays = _block_program(m, w, rid, width, n)
    with jax.enable_x64(True):
        got, stats = fn(arrays, xs)
    got = np.asarray(got)
    assert np.array_equal(got, np.array(spec))
    assert np.array_equal(
        got, crush_indep_ref.map_batch(rm, steps, xs, width, w.tolist()))
    assert int((got == ITEM_NONE).sum()) == holes
    assert (holes > 0) == (case == "few")
    assert np.asarray(stats).tolist() == [
        1, max(rounds), sum(rounds), sum(widths), int(widths[-1] < n)]


def test_a_block_under_the_floor_is_the_one_loop(narrow_from):
    """Under ``MIN_NARROW_WIDTH`` the block lowers to the single
    full-width loop it was: a ``while`` for the rounds and one for
    every position's leaf retries, no gather of unfilled lanes."""
    narrow_from(128)
    m, rid, w, _rm, _steps = _maps("wide")
    text = {}
    for n in (64, 128):
        fn, arrays = _block_program(m, w, rid, 4, n)
        with jax.enable_x64(True):
            text[n] = fn.lower(arrays, np.zeros(n, np.uint32)).as_text()
    assert text[64].count("stablehlo.while") == 1 + 4
    assert "top_k" not in text[64] and "stablehlo.case" not in text[64]
    # two narrow widths, their positions a loop and not unrolled: the
    # rounds, the positions, one leaf loop
    assert text[128].count("stablehlo.while") == (1 + 4) + 2 * 3
    assert text[128].count("top_k") == 2
    assert text[128].count("stablehlo.case") == 2

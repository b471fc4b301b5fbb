"""The erasure-rule sweep: its reference against vectors worked by hand
and against ``crush_ref`` where an indep rule is a firstn rule, its
controls, its readers, and planted faults of its timed path."""

import json

import numpy as np
import pytest

import control
import control_ec
from reference import crush_indep_ref as ir, crush_ref
from reference.crush_ref import ITEM_NONE
from test_rehearsal import run

CELL = "crushtool-10k-ec83-1m"
ERASURE = ("step set_chooseleaf_tries 5\nstep set_choose_tries 100\n"
           "step take root\nstep chooseleaf indep 0 type host\nstep emit\n")


def _map(hosts, per=1, racks=0, domain="host"):
    return crush_ref.build_map({"osds": hosts * per, "hosts": hosts,
                                "racks": racks, "failure_domain": domain})


def _winner(m, bucket_id, x, r):
    """straw2 by its definition: the largest ln(hash) / weight, the
    first of equals."""
    ln = crush_ref.ln16()
    b = m.buckets[bucket_id]
    draws = [-((-int(ln[crush_ref.hash32_3(x, it, r) & 0xFFFF])) // w)
             for it, w in zip(b.items, b.weights)]
    return b.items[draws.index(max(draws))]


def test_the_rule_text_parses_to_crush_h_codes():
    m = _map(4, 2)
    steps = ir.parse_rule("rule ec {\n\tid 1\n\ttype erasure\n"
                          + ERASURE + "}\n", m)
    root = min(m.buckets)
    assert steps == [("set_chooseleaf_tries", 5), ("set_choose_tries", 100),
                     ("take", root), ("chooseleaf_indep", 0, 1), ("emit",)]
    assert ir.step_codes(steps) == [(9, 5, 0), (8, 100, 0), (1, root, 0),
                                    (7, 0, 1), (4, 0, 0)]
    with pytest.raises(ValueError):
        ir.parse_rule("step chooseleaf firstn 0 type host", m)


def test_one_host_fills_one_position_and_leaves_the_hole_in_place():
    m = _map(1)
    steps = ir.parse_rule(ERASURE, m)
    for x in range(50):
        assert ir.do_rule(m, steps, x, 3) == [0, ITEM_NONE, ITEM_NONE]
        assert ir.is_bad(ir.do_rule(m, steps, x, 3), 3)
    assert not ir.is_bad([0], 1) and ir.is_bad([0], 2)
    counts, bad = ir.sweep_counts(m, steps, 0, 50, 3)
    assert counts.tolist() == [50] and bad == 50


def test_two_hosts_by_hand():
    """Position 0 is the root's straw2 winner at r = 0; position 1
    draws at r = 1, 3, 5, ... until it is the other host."""
    m = _map(2)
    steps = ir.parse_rule(ERASURE, m)
    root = min(m.buckets)
    for x in range(200):
        first = _winner(m, root, x, 0)
        other = ({-1, -2} - {first}).pop()
        want = [m.buckets[first].items[0], m.buckets[other].items[0]]
        assert ir.do_rule(m, steps, x, 2) == want
    xs = np.arange(200)
    assert ir.map_batch(m, steps, xs, 2).tolist() == \
        [ir.do_rule(m, steps, int(x), 2) for x in xs]


def test_a_host_whose_osd_is_out_is_a_hole_where_it_would_stand():
    """Two hosts, the second's only OSD weighted out. The draws go r =
    0, 1 in the first round, 2, 3 in the second, ..., position r % 2
    each: host 0 stands at the position that first draws it, and the
    other position can only collide or be out: a hole, in place."""
    m = _map(2)
    steps = ir.parse_rule(ERASURE, m)
    root = min(m.buckets)
    w = [0x10000, 0]
    want = []
    for x in range(100):
        r = next(r for r in range(200) if _winner(m, root, x, r) == -1)
        want.append([0, ITEM_NONE] if r % 2 == 0 else [ITEM_NONE, 0])
        assert ir.do_rule(m, steps, x, 2, w) == want[-1]
    assert [ITEM_NONE, 0] in want and [0, ITEM_NONE] in want
    assert ir.map_batch(m, steps, np.arange(100), 2, w).tolist() == want


@pytest.mark.parametrize("weights", ["in", "out"])
def test_one_position_is_firstns_first_replica(weights):
    """``chooseleaf indep 1`` draws r = ftotal at both levels, as
    ``chooseleaf firstn`` does for its first replica (vary_r 1,
    descend_once): the same device, so the same counts."""
    m = _map(12, 4, racks=3)
    steps = ir.parse_rule("step take root\n"
                          "step chooseleaf indep 1 type host\nstep emit", m)
    w = [0x10000] * m.max_devices
    if weights == "out":
        rng = np.random.default_rng(5)
        for d in rng.choice(m.max_devices, 12, replace=False):
            w[d] = int(rng.choice([0, 0x3000, 0xC000]))
    xs = np.arange(7000, 7600)
    rows = ir.map_batch(m, steps, xs, 1, w)
    for x, row in zip(xs, rows):
        assert crush_ref.do_rule(m, int(x), 1, w) == row.tolist() \
            == ir.do_rule(m, steps, int(x), 1, w)


@pytest.mark.parametrize("num_rep,out", [(6, 0), (11, 0), (6, 5), (11, 5),
                                         (13, 0)])
def test_batch_is_scalar(num_rep, out):
    m = _map(12, 4, racks=4)
    steps = ir.parse_rule(ERASURE, m)
    w = [0x10000] * m.max_devices
    for d in range(out):                 # host 0 out, one OSD of host 1
        w[d] = 0
    xs = np.arange(300, 700)
    rows = ir.map_batch(m, steps, xs, num_rep, w)
    assert rows.tolist() == [ir.do_rule(m, steps, int(x), num_rep, w)
                             for x in xs]
    bad = sum(ir.is_bad(r, num_rep) for r in rows.tolist())
    assert ir.sweep_counts(m, steps, 300, 400, num_rep, w)[1] == bad
    assert (bad == 400) == (num_rep == 13)


def test_the_pool_of_workers_is_the_batch():
    cfg = control._context(CELL, 1, rehearsal=True)[0].config
    ref = ir.IndepReference(cfg["map"], cfg["rule_text"], 0)
    (counts, bad), = ref.counts([(1, 3000)], 6)
    rows = ref.vectors(1, 3000, 6)
    assert bad == 0 and counts.sum() == 18000
    assert np.array_equal(np.bincount(rows.ravel(), minlength=256), counts)
    assert np.array_equal(rows[100:110], ref.vectors(101, 10, 6))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_controls_read_as_said(seed):
    ctx, driver = control._context(CELL, seed, rehearsal=True)
    cfg = ctx.config
    ref = ir.IndepReference(cfg["map"], cfg["rule_text"], 0)
    # the rehearsal's sweep is too short for a float32 ln to move a
    # placement: the control runs on 2^16 ids of the same small map
    ctx.config = cfg = dict(cfg, inputs_per_sweep=1 << 16)
    sweep = [(cfg["min_x"], cfg["inputs_per_sweep"])]
    exact, = ref.counts(sweep, cfg["num_rep"])
    float32, = ref.counts(sweep, cfg["num_rep"], "float32")
    control_ec.control_ec(ctx, driver, ref, "float32_ln", exact, float32)
    assert not ctx.compared.ok
    assert ctx.compared.rows["count_l1"]["value"] > 0
    ctx, driver = control._context(CELL, seed, rehearsal=True)
    ctx.config = cfg
    control_ec.control_ec(ctx, driver, ref, "positions_swapped", exact)
    rows = ctx.compared.rows
    assert not ctx.compared.ok and rows["count_l1"]["value"] == 0
    assert rows["positions_differing"]["value"] == 2 * 512


def test_the_readers_read_the_drivers_deltas_and_nothing_of_a_parent():
    from harness import runner
    ctx, _driver = control._context(CELL, 1, rehearsal=True)
    rounds = runner._load_py(
        runner.BENCH / "layer_metrics" / "crush_indep_rounds_per_block.py")
    share = runner._load_py(
        runner.BENCH / "layer_metrics" / "crush_indep_needed_lane_pct.py")
    ctx.obs.update(sweep_blocks=3, sweep_lanes=3 << 20)   # a parent's
    assert rounds.read(ctx) is None and share.read(ctx) is None
    ctx.obs.update(indep_blocks=3, indep_rounds=12,
                   indep_lane_rounds_needed=3 * 1137000, indep_holes=0)
    assert rounds.read(ctx) == 4.0
    assert share.read(ctx) == pytest.approx(100 * 1137000 / (4 << 20))


FAULTS = [
    ("wrong_rule_swept", {"count_l1", "bad_mappings_gap"}),
    ("positions_swapped", {"positions_differing"}),
    ("sweep_off_its_path", {"sweeps_off_path"}),
    ("hole_dropped", {"bad_mappings_gap", "positions_differing"}),
    ("holes_kept", set()),
]


@pytest.mark.parametrize("fault,numbers", FAULTS, ids=[f for f, _ in FAULTS])
def test_a_fault_in_the_timed_path_is_not_correct(fault, numbers):
    proc = run([fault, "--workload", CELL, "--seed", "77", "--seconds", "2",
                "--trace", "0"], script="benchmark/tests/faulty_run_ec.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    over = {n for n, r in line["compared"].items()
            if r["value"] is None or r["value"] > r["limit"]}
    assert over == numbers, line["compared"]
    assert line["correct"] is (not numbers)


def test_a_program_that_reports_no_hole_as_bad_ends_at_once():
    """What the parent of the PR that added the cell does: it cannot
    run ``--show-bad-mappings`` of an erasure rule, and says so."""
    proc = run(["holes_never_bad", "--workload", CELL, "--seed", "77",
                "--seconds", "2", "--trace", "0"],
               script="benchmark/tests/faulty_run_ec.py")
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == ""
    assert "CRUSH_ITEM_NONE" in proc.stderr

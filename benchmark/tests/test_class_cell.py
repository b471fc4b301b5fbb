"""The device-class sweep: its reference against itself and against
the program's scalar spec on the map the driver builds, its rehearsal,
its controls, its readers, and planted faults of its timed path."""

import json

import numpy as np
import pytest

import control
import control_classes
from reference import crush_class_ref as cr
from test_rehearsal import run

CELL = "crushtool-10k-hybrid-1m"


def _ctx(seed=1):
    return control._context(CELL, seed, rehearsal=True)


def test_the_shadows_are_the_classes_below_each_bucket_by_the_stated_rule():
    cfg = _ctx()[0].config
    ref = cr.ClassReference(cfg["map"], cfg["classes"], cfg["rule_text"], 0)
    assert ref.klass[:16] == ["hdd"] * 12 + ["ssd"] * 4
    base, shadows = ref.base, ref.shadows
    assert len(shadows) == 2 * len(base.buckets) == 42
    low = min(base.buckets)
    hdd = sorted(b.id for (_bid, c), b in shadows.items() if c == "hdd")
    ssd = sorted(b.id for (_bid, c), b in shadows.items() if c == "ssd")
    assert hdd == list(range(low - 21, low))
    assert ssd == list(range(low - 42, low - 21))
    for (bid, c), b in shadows.items():
        # children before their parent: a shadow's items are above it
        assert all(i >= 0 or i > b.id for i in b.items)
        want = [shadows[(i, c)].id if i < 0 else i
                for i in base.buckets[bid].items
                if i < 0 or ref.klass[i] == c]
        assert b.items == want and b.type == base.buckets[bid].type
        assert b.weights == [sum(shadows[(i, c)].weights) if i < 0
                             else 0x10000 for i in base.buckets[bid].items
                             if i < 0 or ref.klass[i] == c]


def test_do_rule_and_map_batch_agree_and_are_the_programs_spec():
    """``do_rule`` and ``map_batch`` lane for lane, truncated and not,
    and both ``crush/mapper_ref.do_rule`` on the map the driver builds
    and reads back."""
    from ceph_tpu.crush import mapper_ref
    from drivers import crush_sweep_classes as drv
    cfg = _ctx()[0].config
    ref = cr.ClassReference(cfg["map"], cfg["classes"], cfg["rule_text"], 0)
    cmap, _args = drv.build_program_map(cfg["map"], cfg["rule_text"], ref)
    drv.same_tree_and_rule(cmap, ref, 1)
    assert cr.shadows_differing(cmap.bucket_names, cmap.buckets,
                                ref.shadows) == 0
    xs = np.arange(5000, 5300)
    rows = cr.map_batch(ref.map, ref.steps, xs, 3)
    wide = cr.map_batch(ref.map, ref.steps, xs, 3, truncate=False)
    assert wide.shape == (300, 4) and (wide[:, :3] == rows).all()
    for x, row, four in zip(xs, rows.tolist(), wide.tolist()):
        assert cr.do_rule(ref.map, ref.steps, int(x), 3) == row \
            == mapper_ref.do_rule(cmap, 1, int(x), 3)
        assert cr.do_rule(ref.map, ref.steps, int(x), 3,
                          truncate=False) == four
        assert [ref.klass[d] for d in row] == ["ssd", "hdd", "hdd"]
    assert np.array_equal(ref.vectors(5000, 300, 3), rows)
    (counts, bad), = ref.counts([(5000, 300)], 3)
    assert bad == 0 and np.array_equal(
        counts, np.bincount(rows.ravel(), minlength=256))


def test_the_rehearsal_end_to_end():
    proc = run(["--workload", CELL, "--seed", str(2 ** 31 + 39),
                "--seconds", "2", "--trace", "1", "--rehearsal"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert list(line["compared"]) == [
        "count_l1", "bad_mappings_gap", "sweeps_off_path",
        "device_fallbacks", "positions_differing", "shadow_ids_differing"]
    window = json.loads(next(
        ln for ln in proc.stderr.splitlines()
        if "] window " in ln).split("] window ", 1)[1])
    assert window["promised_path"] == "xla" and window["takes"] == 2
    assert window["sampled_sweeps"] == 2 and window["shadows_checked"] == 42
    assert window["positions_checked"] == 3 * 512
    # a 4,096-lane block: the ssd block's one slot and the hdd block's
    # three, every lane
    assert window["firstn_slots"] == 4 * 4096 * window["sweep_blocks"]
    assert 0 < window["firstn_loop_lanes"] < window["firstn_slots"] // 50


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_controls_read_as_said(seed):
    ctx, driver = _ctx(seed)
    ref = control_classes.reference(ctx, driver, workers=0)
    try:
        sound = control_classes.sound_answer(ctx, ref)
        for kind in control_classes.CONTROLS:
            ctx, driver = _ctx(seed)
            control_classes.control_classes(ctx, driver, ref, kind, sound)
            rows = {k: r["value"] for k, r in ctx.compared.rows.items()}
            assert not ctx.compared.ok, kind
            assert rows["count_l1"] > 0 and rows["positions_differing"] > 0
            if kind == "emit_not_truncated":
                # one HDD more a mapping; every position of a 4-wide block
                assert rows["count_l1"] == 4096
                assert rows["positions_differing"] == 3 * 512
                assert rows["shadow_ids_differing"] == 0
            else:
                assert rows["shadow_ids_differing"] == 42
    finally:
        ref.close()


def test_the_readers_read_the_drivers_deltas_and_nothing_of_a_parent():
    from harness import runner
    ctx, _driver = _ctx()
    ppm = runner._load_py(
        runner.BENCH / "layer_metrics" / "crush_firstn_loop_lanes_ppm.py")
    rounds = runner._load_py(runner.BENCH / "layer_metrics"
                             / "crush_firstn_loop_rounds_per_block.py")
    ctx.obs.update(sweep_blocks=5, sweep_lanes=5 << 20)     # a parent's
    assert ppm.read(ctx) is None and rounds.read(ctx) is None
    ctx.obs.update(firstn_slots=5 * 4 << 20, firstn_loop_lanes=35,
                   firstn_loop_rounds=15)
    assert ppm.read(ctx) == pytest.approx(1e6 * 35 / (5 * 4 << 20))
    assert rounds.read(ctx) == 3


FAULTS = [
    ("shadow_ids_regenerated",
     {"count_l1", "positions_differing", "shadow_ids_differing"}),
    ("emit_not_truncated", {"count_l1", "positions_differing"}),
    ("blocks_swapped", {"count_l1", "positions_differing"}),
    ("sweep_off_its_path", {"sweeps_off_path"}),
]


@pytest.mark.parametrize("fault,numbers", FAULTS, ids=[f for f, _ in FAULTS])
def test_a_fault_in_the_timed_path_is_not_correct(fault, numbers):
    proc = run([fault, "--workload", CELL, "--seed", "77", "--seconds", "2",
                "--trace", "0"], script="benchmark/tests/faulty_run_classes.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    over = {n for n, r in line["compared"].items()
            if r["value"] is None or r["value"] > r["limit"]}
    assert over == numbers, line["compared"]
    assert line["correct"] is False


def test_a_program_that_makes_its_own_shadow_ids_ends_at_once():
    """What a program from before stated shadow ids does: its
    ``crushtool -c`` skips ``id <n> class <c>``, and the run says so
    before it builds anything."""
    proc = run(["never_honoured", "--workload", CELL, "--seed", "77",
                "--seconds", "2", "--trace", "0"],
               script="benchmark/tests/faulty_run_classes.py")
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == ""
    assert "does not keep the map's shadow ids" in proc.stderr
    assert "set-up map" not in proc.stderr

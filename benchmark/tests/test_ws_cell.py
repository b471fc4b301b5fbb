"""The weight-set sweep: its reference against the program's scalar
spec with the same weight-set, its rehearsal, its controls, its readers,
and planted faults of its timed path."""

import json

import numpy as np
import pytest

import control
import control_ws
from reference import crush_ref, crush_ws_ref as wr
from test_rehearsal import run

CELL = "crushtool-10k-choose-args-4m"


def _cfg():
    return control._context(CELL, 1, rehearsal=True)[0].config


def test_the_weight_set_is_the_generators_and_the_sums_below():
    cfg = _cfg()
    base, vectors, m = wr.build(cfg["map"], cfg["weight_set"])
    f = np.random.default_rng(10240).uniform(0.90, 1.10, 256)
    osd_w = [int(65536 * v) for v in f]
    assert wr.osd_weights(cfg["map"], cfg["weight_set"]) == osd_w
    assert set(vectors) == set(base.buckets) and len(vectors) == 21
    for bid, b in base.buckets.items():
        assert m.buckets[bid].items == b.items
        assert m.buckets[bid].weights == vectors[bid] != b.weights
        for item, w in zip(b.items, vectors[bid]):
            assert w == (osd_w[item] if item >= 0 else sum(vectors[item]))
    root = min(base.buckets)
    assert sum(vectors[root]) == sum(osd_w)
    assert all(0.90 * 65536 - 1 <= w < 1.10 * 65536 for w in osd_w)


def test_the_reference_is_the_programs_scalar_spec_with_the_weight_set():
    """``crush_ws_ref`` (the tree with weights substituted, crush_ref's
    loops) against ``crush/mapper_ref.do_rule(..., choose_args=)`` on
    the map the driver builds and reads back, scalar and batched."""
    from ceph_tpu.crush import mapper_ref
    from drivers import crush_sweep_ws
    cfg = _cfg()
    ref = wr.WeightSetReference(cfg["map"], cfg["weight_set"], 0)
    cmap, _args = crush_sweep_ws.build_program_map(
        cfg["map"], cfg["weight_set"], ref.osd_weights)
    crush_sweep_ws.same_map(cmap, ref)
    xs = np.arange(9000, 9400)
    rows = wr.map_batch(ref.map, xs, 3)
    moved = 0
    for x, row in zip(xs, rows):
        want = mapper_ref.do_rule(cmap, 0, int(x), 3,
                                  choose_args=cmap.choose_args[-1])
        assert wr.do_rule(ref.map, int(x), 3) == want == row.tolist()
        moved += want != mapper_ref.do_rule(cmap, 0, int(x), 3)
    assert moved > 10                    # the weight-set does something
    assert np.array_equal(ref.vectors(9000, 400, 3), rows)
    (counts, bad), = ref.counts([(9000, 400)], 3)
    assert bad == 0 and np.array_equal(
        counts, np.bincount(rows.ravel(), minlength=256))
    # the tree as built is crush_ref's own answer
    assert np.array_equal(ref.vectors(9000, 400, 3, which="none"),
                          crush_ref.map_batch(ref.base, xs, 3))


def test_a_weight_set_installed_wrongly_stops_the_run_before_the_window():
    from drivers import crush_sweep_ws
    cfg = _cfg()
    ref = wr.WeightSetReference(cfg["map"], cfg["weight_set"], 0)
    cmap, _args = crush_sweep_ws.build_program_map(
        cfg["map"], cfg["weight_set"], ref.osd_weights)
    assert wr.vectors_differing(cmap.choose_args[-1], ref.weight_set) == 0
    cmap.choose_args[-1][-2].weight_set[0][3] -= 1
    assert wr.vectors_differing(cmap.choose_args[-1], ref.weight_set) == 1
    with pytest.raises(RuntimeError, match="1 entries"):
        crush_sweep_ws.same_map(cmap, ref)
    cmap.choose_args[-1][-2].weight_set[0][3] += 1
    del cmap.choose_args[-1][-21]        # the root's vector missing
    assert wr.vectors_differing(cmap.choose_args[-1], ref.weight_set) == 4
    assert wr.vectors_differing(None, ref.weight_set) == 256 + 16 + 4
    cmap.choose_args[0] = cmap.choose_args.pop(-1)
    with pytest.raises(RuntimeError, match="weight-sets"):
        crush_sweep_ws.same_map(cmap, ref)


@pytest.mark.parametrize("seed", [0, 35, 2 ** 31 + 35])
def test_a_runs_first_id_is_drawn_from_its_seed(seed):
    """The issue's traffic: S in [2n, 2^31), so the warm-up's two sweeps
    lie below it and no run maps another's ids; the file gives none."""
    import types
    from drivers import crush_sweep_ws
    n = int(_cfg()["inputs_per_sweep"])
    at = crush_sweep_ws.origin_of(types.SimpleNamespace(seed=seed), n)
    assert 2 * n <= at < 1 << 31
    assert at == int(np.random.default_rng(seed).integers(2 * n, 1 << 31))
    assert at != crush_sweep_ws.origin_of(
        types.SimpleNamespace(seed=seed + 1), n)
    ctx, _driver = control._context(CELL, seed, True)
    assert "origin" not in ctx.traffic


def test_the_rehearsal_end_to_end():
    proc = run(["--workload", CELL, "--seed", str(2 ** 31 + 35),
                "--seconds", "2", "--trace", "1", "--rehearsal"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert list(line["compared"]) == [
        "count_l1", "bad_mappings_gap", "sweeps_off_path",
        "device_fallbacks", "positions_differing", "weight_set_differing"]
    window = json.loads(next(
        ln for ln in proc.stderr.splitlines()
        if "] window " in ln).split("] window ", 1)[1])
    assert window["choose_args"] == -1 and window["sampled_sweeps"] == 2
    assert window["positions_checked"] == 3 * 512
    for key in ("kernel_flagged_lanes", "kernel_fallback_blocks",
                "kernel_fallback_overflows", "sweep_lanes"):
        assert key in window


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_controls_read_as_said(seed):
    ctx, driver = control._context(CELL, seed, rehearsal=True)
    # the rehearsal's sweep is too short for a float32 ln to move a
    # placement: the controls run on 2^16 ids of the same small map
    cfg = dict(ctx.config, inputs_per_sweep=1 << 16)
    ctx.config = cfg
    ref = control_ws.reference(ctx, driver, workers=0)
    sound = control_ws.sound_answer(ctx, driver, ref)
    said = {"weight_set_ignored": 256 + 16 + 4, "float32_ln": 0}
    for kind in control_ws.CONTROLS:
        ctx, driver = control._context(CELL, seed, rehearsal=True)
        ctx.config = cfg
        control_ws.control_ws(ctx, driver, ref, kind, sound)
        rows = ctx.compared.rows
        assert not ctx.compared.ok, kind
        assert rows["count_l1"]["value"] > 0, kind
        if kind in said:
            assert rows["weight_set_differing"]["value"] == said[kind]
        else:                            # most entries leave their value
            assert rows["weight_set_differing"]["value"] > 200


def test_the_readers_read_the_drivers_deltas_and_nothing_of_a_parent():
    from harness import runner
    ctx, _driver = control._context(CELL, 1, rehearsal=True)
    ppm = runner._load_py(
        runner.BENCH / "layer_metrics" / "crush_flagged_lanes_ppm.py")
    over = runner._load_py(
        runner.BENCH / "layer_metrics" / "crush_fallback_overflows.py")
    ctx.obs.update(sweep_blocks=4, sweep_lanes=4 << 21)   # a parent's
    assert ppm.read(ctx) is None and over.read(ctx) is None
    ctx.obs.update(kernel_flagged_lanes=2600, kernel_fallback_blocks=4,
                   kernel_fallback_overflows=0)
    assert ppm.read(ctx) == pytest.approx(1e6 * 2600 / (4 << 21))
    assert over.read(ctx) == 0
    ctx.obs.update(kernel_fallback_overflows=3)
    assert over.read(ctx) == 3


FAULTS = [
    ("weight_set_ignored", {"count_l1", "positions_differing"}),
    ("positions_swapped", {"positions_differing"}),
    ("vector_entry_off_by_one", {"weight_set_differing"}),
    ("sweep_off_its_path", {"sweeps_off_path"}),
]


@pytest.mark.parametrize("fault,numbers", FAULTS, ids=[f for f, _ in FAULTS])
def test_a_fault_in_the_timed_path_is_not_correct(fault, numbers):
    proc = run([fault, "--workload", CELL, "--seed", "77", "--seconds", "2",
                "--trace", "0"], script="benchmark/tests/faulty_run_ws.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    over = {n for n, r in line["compared"].items()
            if r["value"] is None or r["value"] > r["limit"]}
    assert over == numbers, line["compared"]
    assert line["correct"] is False


def test_a_program_that_does_not_honour_the_weight_set_ends_at_once():
    """What the parent of the PR that added the cell does: its
    ``crushtool --test`` tests the unbalanced tree, and the driver says
    so before it builds anything."""
    proc = run(["never_honoured", "--workload", CELL, "--seed", "77",
                "--seconds", "2", "--trace", "0"],
               script="benchmark/tests/faulty_run_ws.py")
    assert proc.returncode not in (0, 3)
    assert proc.stdout.strip() == ""
    assert "does not honour the map's choose_args" in proc.stderr
    assert "set-up map" not in proc.stderr

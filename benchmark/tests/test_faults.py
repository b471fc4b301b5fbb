"""``correct`` has to come out false when the timed path is broken
underneath a run (faulty_run.py), and for every control
(benchmark/control.py)."""

import json

import numpy as np
import pytest

import control
from test_rehearsal import CELLS, run

FAULTS = [
    ("crushtool-10k-1m", "sweep_answer_altered", {"count_l1"}),
    ("crushtool-10k-1m", "sweep_half_left_out", {"count_l1"}),
    ("crush-pod-sweep-8m", "sweep_no_exchange", {"count_l1"}),
    ("ec83-write-4m-t16", "write_state_unchanged",
     {"shards_missing", "shards_missing_at_ack"}),
    ("ec83-write-4m-t16", "encode_answer_altered", {"shards_differing"}),
    ("ec83-degraded-read-4m-t16", "read_answer_altered",
     {"reads_differing"}),
]


@pytest.mark.parametrize("cell,fault,numbers", [
    f for f in FAULTS if f[0] in CELLS], ids=lambda v: str(v))
def test_a_fault_in_the_timed_path_is_not_correct(cell, fault, numbers):
    proc = run([fault, "--workload", cell, "--seed", "77", "--seconds", "2",
                "--trace", "0"], devices=CELLS[cell]["chips"],
               script="benchmark/tests/faulty_run.py")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    over = {n for n, r in line["compared"].items()
            if r["value"] is None or r["value"] > r["limit"]}
    assert numbers <= over, line["compared"]


@pytest.mark.parametrize("cell", [
    c for c in sorted(CELLS) if "ec83" in c])
def test_the_rados_control_is_not_correct(cell):
    for seed in (1, 2, 3):
        ctx, driver = control._context(cell, seed, rehearsal=True)
        control.control_rados(ctx, driver)
        assert not ctx.compared.ok


def test_the_float32_ln_control_is_not_correct():
    from reference import crush_ref
    cell = next(c for c in sorted(CELLS) if CELLS[c]["chips"] == 1
                and "crush" in c)
    for seed in (1, 2, 3):
        ctx, driver = control._context(cell, seed, rehearsal=False)
        # the cell's own map, a sixteenth of its sweep: what a test holds
        ctx.config = dict(ctx.config, inputs_per_sweep=1 << 16)
        ref = crush_ref.SweepReference(ctx.config["map"], 0)
        control.control_crush(ctx, driver, ref)
        assert not ctx.compared.ok
        assert ctx.compared.rows["count_l1"]["value"] > 0


def test_a_sound_answer_passes_the_same_comparison():
    from reference import crush_ref
    ctx, driver = control._context("crushtool-10k-1m", 5, rehearsal=True)
    ref = crush_ref.SweepReference(ctx.config["map"], 0)
    n = int(ctx.config["inputs_per_sweep"])
    (c, bad), = ref.counts([(12345, n)], 3)
    ctx.obs["sweeps_off_path"] = 0
    driver.compare(ctx, [(12345, n, np.array(c), bad, "x")], [0], [(c, bad)])
    assert ctx.compared.ok

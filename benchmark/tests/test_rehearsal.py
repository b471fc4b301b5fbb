"""A CPU rehearsal of each cell at a tiny size: the last line has the
contract's keys and no device metric; a run without ``--rehearsal``
and without a TPU fails and prints no result."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def run(argv, devices=1, script="benchmark/run.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, script, *argv], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal_prints_the_keys_and_no_metric(cell, trace):
    proc = run(["--workload", cell, "--seed", str(2 ** 31 + 11),
                "--seconds", "2", "--trace", str(trace), "--rehearsal"],
               devices=CELLS[cell]["chips"])
    line = last_line(proc)
    assert list(line)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert "breakdown" not in line and "busy_s" not in line["device"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == CELLS[cell]["chips"]
    for row in line["compared"].values():
        assert set(row) == {"value", "limit"}
    # the same numbers, each beside its limit, end standard error
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "(limit " in t for t in tail)


def test_without_a_chip_a_run_fails_and_prints_no_result():
    cell = sorted(CELLS)[0]
    proc = run(["--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_an_unknown_cell_is_refused():
    proc = run(["--workload", "no-such-cell", "--seed", "1", "--seconds",
                "1", "--trace", "0"])
    assert proc.returncode != 0 and proc.stdout.strip() == ""

"""chip_smoke.py must not rot between chip runs: its phase functions
run here at the script's rehearsal size. The script itself has no CPU
branch — the platform and engine expectations are swapped HERE, to what
the CPU backend resolves (``backend=auto`` -> bitmatmul, no fused CRUSH
kernel -> the XLA rule VM)."""

import json

import pytest

import chip_smoke


@pytest.fixture
def cpu_expectations(monkeypatch):
    monkeypatch.setattr(chip_smoke, "EXPECT", {
        "platform": "cpu", "ec_backend": "bitmatmul", "crush_path": "xla"})


def _last_json(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("phase", ["served", "placement", "kernels",
                                   "sharded"])
def test_phase_at_rehearsal_size(cpu_expectations, capsys, phase):
    line = getattr(chip_smoke, f"phase_{phase}")(chip_smoke.REHEARSAL, 22)
    assert line["phase"] == phase
    assert _last_json(capsys) == json.loads(json.dumps(line))
    if phase == "served":
        assert line["degraded_reads"]["ec83"] == \
            chip_smoke.REHEARSAL.degraded_reads
        assert line["decode_ops"] > 0
        # the fact the next perf issue starts from: stripe_unit 4096
        # never reaches the fused kernel
        assert all("xla bitmatmul" in e for e in line["ec_engines"])
    if phase == "placement":
        # the 11-wide indep block ran (under the narrowing floor here)
        assert line["indep_pgs"] == chip_smoke.REHEARSAL.indep_pgs
        assert line["indep_narrow_widths"] == []
        from ceph_tpu.crush.mapper import narrow_widths
        assert narrow_widths(chip_smoke.FULL.indep_pgs) == (1 << 13, 1 << 9)
    if phase == "sharded":
        assert line["sweep_devices"] == line["encode_devices"] == 4


def test_without_a_chip_the_script_fails(capsys):
    """No accelerator: non-zero exit and the last line says ok: false —
    never a result."""
    assert chip_smoke.main([]) == 1
    last = _last_json(capsys)
    assert last["ok"] is False and "device" not in last


def test_a_moved_fallback_counter_fails_the_phase(cpu_expectations, capsys):
    """Bytes can match with the chip's kernels dead (the degrade ladders
    serve from the host), so emit() fails on the counters."""
    from ceph_tpu.crush.mapper import PERF
    before = chip_smoke._counters()
    PERF.inc("kernel_exec_failures")
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel_exec_failures"):
        chip_smoke.emit("x", 0.0, before, "cpu")

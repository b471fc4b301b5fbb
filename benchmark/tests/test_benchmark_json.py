"""BENCHMARK.json against the contract's limits, and against the files
it names."""

import json
import re

import pytest

from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmark"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert all(_line(w) for w in SPEC["command"])


def test_names_units_and_whys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(r) for r in c["reduced"])
        assert len(c["reduced"]) <= 16
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
        names.append(w["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))


def test_end_to_end_metrics():
    by = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in by and "workloads" not in by["setup_s"]
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_cell_reports_enough():
    cells = {w["name"] for w in SPEC["workloads"]}
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in SPEC["end_to_end"]}
    for cell in cells:
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s") >= 1
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in e2e[m["moves"]]
    for cell in cells:
        assert any(cell in m.get("workloads", e2e[m["moves"]])
                   for m in SPEC["per_layer"])


def test_files_are_there():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("benchmark/configs/")
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] and cfg["guarantees"]
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for w in SPEC["workloads"]:
        tr = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "drivers" / f"{tr['driver']}.py").is_file()
    for m in SPEC["per_layer"]:
        base = m["name"].partition(".")[0]
        assert (BENCH / "layer_metrics" / f"{base}.py").is_file(), base


def test_run_py_names_no_cell_config_or_metric():
    text = (BENCH / "run.py").read_text() + \
        (BENCH / "harness" / "runner.py").read_text()
    words = [w["name"] for w in SPEC["workloads"]] + \
        [c["name"] for c in SPEC["configs"]] + \
        [m["name"] for m in SPEC["per_layer"]] + \
        [m["name"] for m in SPEC["end_to_end"] if m["name"] != "setup_s"]
    for word in words:
        assert word not in text, word


def test_roofline_names_follow_the_rule():
    for m in SPEC["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


@pytest.mark.parametrize("path", sorted(
    str(p.relative_to(ROOT)) for p in BENCH.rglob("*")
    if p.is_file() and "__pycache__" not in p.parts))
def test_file_names(path):
    assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)

#!/usr/bin/env python3
"""``faulty_run.py`` for the device-class sweep: a rehearsal run with the
placement path broken underneath; ``correct`` has to come out false, by
the fault's own numbers.

    python benchmark/tests/faulty_run_classes.py <fault> --workload ... --seed ...
"""

import pathlib
import re
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

STATED = re.compile(r"^\s*id\s+-?\d+\s+class\s+\S+\s*$", re.M)


def _compile_ignoring_stated_ids(also_the_probe: bool):
    """``crushtool -c`` as it was before it read ``id <n> class <c>``:
    the lines are dropped and the shadows take ids in the order the
    rules take their classes."""
    from ceph_tpu.crush import compiler
    real = compiler.compile_crushmap

    def compile_crushmap(text):
        if also_the_probe or text.count("\ndevice ") > 2:
            text = STATED.sub("", text)
        return real(text)
    compiler.compile_crushmap = compile_crushmap


def shadow_ids_regenerated():
    """The map's shadow ids ignored past the driver's probe: the
    hybrid rule takes its ssd class first, so the ssd shadows take the
    ids the text gave the hdd ones."""
    _compile_ignoring_stated_ids(False)


def never_honoured():
    """The compiler never reads a stated shadow id, as before the cell:
    the run ends at once, with no result line."""
    _compile_ignoring_stated_ids(True)


def emit_not_truncated():
    """A rule of two take/emit blocks emits every item its blocks
    chose: the second block's third HDD is counted, and the kept
    mappings are four wide."""
    import functools
    from ceph_tpu.crush import mapper
    from ceph_tpu.crush.types import OP_TAKE
    real = mapper._rule_body

    @functools.lru_cache(maxsize=None)
    def rule_body(steps, result_max, *a, **kw):
        if sum(s[0] == OP_TAKE for s in steps) > 1:
            result_max += 1
        return real(steps, result_max, *a, **kw)
    mapper._rule_body = rule_body


def blocks_swapped():
    """The tester's map comes to it with the rule's two blocks in the
    other order: three HDDs, the SSD never emitted."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.__init__

    def init(self, crush_map, *a, **kw):
        if crush_map.max_devices > 2 and 1 in crush_map.rules:
            steps = crush_map.rules[1].steps
            crush_map.rules[1].steps = steps[3:] + steps[:3]
        real(self, crush_map, *a, **kw)
    CrushTester.__init__ = init


def sweep_off_its_path():
    """Every second sweep says another engine served it than the one
    ``mapping_path`` promised."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.test
    calls = []

    def test(self, *a, **kw):
        res = real(self, *a, **kw)
        calls.append(1)
        if len(calls) % 2:
            self.mapper.last_map_path = "scalar"
        return res
    CrushTester.test = test


FAULTS = {f.__name__: f for f in (
    shadow_ids_regenerated, never_honoured, emit_not_truncated,
    blocks_swapped, sweep_off_its_path)}

if __name__ == "__main__":
    t0 = time.perf_counter()
    FAULTS[sys.argv[1]]()
    from harness.runner import main
    sys.exit(main(sys.argv[2:] + ["--rehearsal"], t_start=t0))

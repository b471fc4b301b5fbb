#!/usr/bin/env python3
"""``faulty_run.py`` for the erasure-rule sweep: a rehearsal run with
the placement path broken underneath; ``correct`` has to come out
false. ``holes_kept`` is no fault: the rehearsal asked for more
positions than the map has hosts, which a sound program passes.

    python benchmark/tests/faulty_run_ec.py <fault> --workload ... --seed ...
"""

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def wrong_rule_swept():
    """The sweeps map the map's rule 0, the replicated one."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.test

    def test(self, rule, *a, **kw):
        return real(self, 0, *a, **kw)
    CrushTester.test = test


def positions_swapped():
    """Every kept mapping comes back with its first two positions
    exchanged: the counts of a sweep cannot tell."""
    import numpy as np
    from ceph_tpu.crush.mapper import Mapper
    real = Mapper.map_pgs

    def map_pgs(self, ruleno, xs, result_max):
        out = np.array(real(self, ruleno, xs, result_max))
        out[:, [0, 1]] = out[:, [1, 0]]
        return out
    Mapper.map_pgs = map_pgs


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


def _more_positions_than_hosts():
    """The rehearsal's rule asked for 17 positions of 16 hosts: every
    mapping has a hole."""
    from harness import runner
    real = runner.load_cell

    def load_cell(name, rehearsal=False):
        spec, cell, config, traffic = real(name, rehearsal)
        return spec, cell, dict(config, num_rep=17), traffic
    runner.load_cell = load_cell


def holes_kept():
    _more_positions_than_hosts()


def hole_dropped():
    """A holed mapping is not reported as bad (what the tester said
    before it counted as upstream does), and a kept mapping comes back
    with its holes closed up, firstn's way."""
    import numpy as np
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.crush.tester import CrushTester
    from ceph_tpu.crush.types import ITEM_NONE
    _more_positions_than_hosts()
    real_test, real_map = CrushTester.test, Mapper.map_pgs

    def test(self, *a, **kw):
        res = real_test(self, *a, **kw)
        if res.total_x > 8:             # the driver's probe still passes
            res.bad_mappings = 0
        return res

    def map_pgs(self, ruleno, xs, result_max):
        out = np.array(real_map(self, ruleno, xs, result_max))
        order = np.argsort(out == ITEM_NONE, axis=1, kind="stable")
        return np.take_along_axis(out, order, axis=1)
    CrushTester.test, Mapper.map_pgs = test, map_pgs


def holes_never_bad():
    """The tester never reports a holed mapping as bad, as before it
    counted the way upstream does: the run ends at once, with no
    result line."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.test

    def test(self, *a, **kw):
        res = real(self, *a, **kw)
        res.bad_mappings = 0
        return res
    CrushTester.test = test


FAULTS = {f.__name__: f for f in (
    wrong_rule_swept, positions_swapped, sweep_off_its_path, holes_kept,
    hole_dropped, holes_never_bad)}

if __name__ == "__main__":
    t0 = time.perf_counter()
    FAULTS[sys.argv[1]]()
    from harness.runner import main
    sys.exit(main(sys.argv[2:] + ["--rehearsal"], t_start=t0))

#!/usr/bin/env python3
"""``faulty_run.py`` for the weight-set sweep: a rehearsal run with the
placement path broken underneath; ``correct`` has to come out false, by
the fault's own number.

    python benchmark/tests/faulty_run_ws.py <fault> --workload ... --seed ...
"""

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def weight_set_ignored():
    """The mapper of any map but the driver's two-OSD probe is built
    without the weight-set its tester resolved: the sweeps place on the
    unbalanced tree."""
    from ceph_tpu.crush.mapper import Mapper
    real = Mapper.__init__

    def init(self, crush_map, *a, **kw):
        if crush_map.max_devices > 2:
            kw["choose_args"] = None
        real(self, crush_map, *a, **kw)
    Mapper.__init__ = init


def never_honoured():
    """The tester never hands its mapper a weight-set, as before the
    cell: the run ends at once, with no result line."""
    from ceph_tpu.crush.mapper import Mapper
    real = Mapper.__init__

    def init(self, crush_map, *a, **kw):
        kw["choose_args"] = None
        real(self, crush_map, *a, **kw)
    Mapper.__init__ = init


def positions_swapped():
    """Every kept mapping comes back with its first two positions
    exchanged, the primary among them: the counts of a sweep cannot
    tell."""
    import numpy as np
    from ceph_tpu.crush.mapper import Mapper
    real = Mapper.map_pgs

    def map_pgs(self, ruleno, xs, result_max):
        out = np.array(real(self, ruleno, xs, result_max))
        out[:, [0, 1]] = out[:, [1, 0]]
        return out
    Mapper.map_pgs = map_pgs


def vector_entry_off_by_one():
    """The tester's map comes to it with one entry of one host's
    vector a 65,536th of an OSD heavier than the configuration's."""
    from ceph_tpu.crush.tester import CrushTester
    real = CrushTester.__init__

    def init(self, crush_map, *a, **kw):
        if crush_map.max_devices > 2:
            crush_map.choose_args[-1][-3].weight_set[0][1] += 1
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
    weight_set_ignored, never_honoured, positions_swapped,
    vector_entry_off_by_one, sweep_off_its_path)}

if __name__ == "__main__":
    t0 = time.perf_counter()
    FAULTS[sys.argv[1]]()
    from harness.runner import main
    sys.exit(main(sys.argv[2:] + ["--rehearsal"], t_start=t0))

"""The crushtool --test engine, batched.

ref: src/crush/CrushTester.{h,cc} (CrushTester::test) — loops x over
[min_x, max_x], runs the rule, and aggregates per-device utilization,
bad-mapping counts and timing. Here the whole x range is one (or a few)
batched mapper calls on the accelerator instead of a scalar loop.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.types import CrushMap, ITEM_NONE
from ceph_tpu.utils import tracing
from ceph_tpu.utils.logging import get_logger

log = get_logger("crush")


@dataclasses.dataclass
class TestResult:
    rule: int
    num_rep: int
    total_x: int
    device_counts: np.ndarray          # (max_devices,) placements per device
    bad_mappings: int                  # x's with < num_rep devices or a hole
    seconds: float
    mappings: np.ndarray | None = None  # (N, num_rep) if requested
    path: str = ""                     # the engine that served the sweep
    min_x: int = 0
    indep: bool = False                # the rule's results keep holes
    choose_args: int | None = None     # id of the weight-set that served

    @property
    def mappings_per_second(self) -> float:
        return self.total_x / self.seconds if self.seconds else float("inf")

    def utilization_summary(self) -> dict:
        c = self.device_counts
        active = c[c > 0]
        expected = c.sum() / max(len(c), 1)
        return {
            "devices": int(len(c)),
            "active_devices": int(len(active)),
            "placements": int(c.sum()),
            "expected_per_device": float(expected),
            "min": int(c.min()) if len(c) else 0,
            "max": int(c.max()) if len(c) else 0,
            "stddev": float(c.std()),
        }


def _bad_rows(mappings: np.ndarray) -> np.ndarray:
    """(N,) bool: the result has fewer than num_rep entries or holds a
    CRUSH_ITEM_NONE. A mapped block is ITEM_NONE-filled to num_rep
    columns, so both are one test."""
    return (mappings == ITEM_NONE).any(axis=1)


class CrushTester:
    """ref: src/crush/CrushTester.h CrushTester."""

    def __init__(self, crush_map: CrushMap,
                 device_weights: np.ndarray | None = None,
                 batch: int | None = None):
        self.map = crush_map
        # the map's weight-set, resolved as upstream's tool resolves it:
        # CrushTester::test calls do_rule(..., 0), so the set of id 0,
        # else the compat set (-1), else none. A balanced map is tested
        # as balanced, with no flag.
        self.choose_args_key = crush_map.choose_args_with_fallback(0)
        # batch bounds device memory: it becomes the Mapper's tile size
        # (None = auto-sized from the map's bucket width)
        self.mapper = Mapper(crush_map, device_weights, block=batch,
                             choose_args=self.choose_args_key)
        self.batch = self.mapper.block
        from ceph_tpu.utils.perf_counters import (PerfCountersBuilder,
                                                  PerfCountersCollection)
        existing = PerfCountersCollection.instance().get("crush_tester")
        self.perf = existing or (
            PerfCountersBuilder("crush_tester")
            .add_u64_counter("mappings", "PGs mapped")
            .add_u64_counter("bad_mappings", "short or holed results")
            .add_time("map_seconds", "time in test sweeps")
            .create_perf_counters())

    def test(self, rule: int, num_rep: int, min_x: int = 0,
             max_x: int = 1023, keep_mappings: bool = False) -> TestResult:
        """Aggregated sweep over [min_x, max_x].

        Without keep_mappings this is ONE device program (Mapper.sweep):
        per-device counts accumulate on the device and only
        the (max_devices,) count vector is read back — round 1 shipped
        every (N, rep) mapping block to the host and bincounted there.

        A mapping is bad as upstream's CrushTester::test reports it:
        fewer than num_rep entries (a short firstn result) or any
        CRUSH_ITEM_NONE (an indep rule's hole), so ``crushtool --test
        --show-bad-mappings`` says of an EC rule what it says upstream.

        The call is the section ``crush.test``, whose self time is the
        tester's own (counters, the result, the log line); the reads
        that bring the counts and the bad count home are its
        ``crush.readback``.
        """
        with tracing.section("crush.test", service="crush"):
            n = max_x - min_x + 1
            t0 = time.perf_counter()
            if keep_mappings:
                kept, path = self.mapper.map_pgs_path(
                    rule, np.arange(min_x, max_x + 1, dtype=np.uint32),
                    num_rep)
                with tracing.section("crush.readback", service="crush"):
                    kept = np.asarray(kept)
                counts = np.bincount(kept[kept != ITEM_NONE],
                                     minlength=self.map.max_devices)
                bad = int(_bad_rows(kept).sum())
            else:
                counts_dev, bad_dev, path = self.mapper.sweep_path(
                    rule, min_x, n, num_rep)
                with tracing.section("crush.readback", service="crush"):
                    counts = np.asarray(counts_dev)  # the execution anchor
                    bad = int(bad_dev)
                kept = None
            seconds = time.perf_counter() - t0
            self.perf.inc("mappings", n)
            self.perf.inc("bad_mappings", bad)
            self.perf.tinc("map_seconds", seconds)
            res = TestResult(
                rule=rule, num_rep=num_rep, total_x=n,
                device_counts=counts, bad_mappings=bad, seconds=seconds,
                mappings=kept, path=path, min_x=min_x,
                indep=not self.mapper.rule_is_firstn(rule),
                choose_args=self.choose_args_key)
            log.dout(5, "test done", rule=rule, num_rep=num_rep, n=n,
                     secs=round(seconds, 3))
            return res

    def bad_mapping_lines(self, res: TestResult) -> list[str]:
        """``--show-bad-mappings``' line for every bad mapping of
        ``res``: ``bad mapping rule R x X num_rep N result [...]``, an
        indep rule's holes as CRUSH_ITEM_NONE (2147483647), a firstn
        rule's short result as the devices it got (ref: CrushTester.cc
        CrushTester::test). A sweep keeps no mapping, so where it
        counted a bad one the range is mapped once more, keeping them."""
        if not res.bad_mappings:
            return []
        if res.mappings is None:
            res = self.test(res.rule, res.num_rep, res.min_x,
                            res.min_x + res.total_x - 1, keep_mappings=True)
        lines = []
        for i in np.nonzero(_bad_rows(res.mappings))[0]:
            row = [int(d) for d in res.mappings[i]
                   if res.indep or d != ITEM_NONE]
            lines.append(
                f"bad mapping rule {res.rule} x {res.min_x + int(i)} "
                f"num_rep {res.num_rep} result "
                f"[{','.join(map(str, row))}]")
        return lines

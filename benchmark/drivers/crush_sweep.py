"""Traffic kind ``crush_sweep``: back-to-back aggregated CRUSH sweeps.

The window drives what ``crushtool --test`` drives after it has built
the map -- ``CrushTester.test(rule, num_rep, min_x, max_x)`` on one
held tester (``entry: crushtester``) -- or the sweep over a device mesh,
``crush.sharded_sweep.sharded_sweep`` (``entry: sharded_sweep``). Every
sweep maps ``inputs_per_sweep`` consecutive ids from a start that steps
from a seeded origin, so no two sweeps map the same ids; the start is a
traced argument of the program, so a new start is no new program.

A sweep returns the per-device placement counts and the number of bad
mappings and nothing else, so that is what is compared: for a sample
of the window's sweeps drawn from the seed the plain reference maps every input of the sweep on CPU workers
and the counts have to be equal, device for device.
"""

from __future__ import annotations

import os
import time

import numpy as np

from harness import counters
from reference import crush_ref


def _build_program_map(desc: dict):
    """The map as ``crushtool --build`` makes it, through crushtool's
    own argument parser and builder."""
    from ceph_tpu.bench import crushtool
    argv = ["--build", "--num-osds", str(desc["osds"]),
            "--hosts", str(desc["hosts"]), "--racks", str(desc["racks"]),
            "--alg", desc.get("alg", "straw2")]
    if "batch" in desc:                 # rehearsal only: the tool's default
        argv += ["--batch", str(desc["batch"])]
    args = crushtool.parse_args(argv)
    return crushtool.build_map(args), args


def _same_map(program_map, ref_map) -> None:
    """The reference builds its own map from the description; the two
    have to be the same tree, or the comparison compares nothing."""
    if set(program_map.buckets) != set(ref_map.buckets):
        raise RuntimeError("the program's map and the reference's differ "
                           "in their bucket ids")
    for bid, rb in ref_map.buckets.items():
        pb = program_map.buckets[bid]
        if (list(pb.items) != rb.items or list(pb.weights) != rb.weights
                or pb.type != rb.type):
            raise RuntimeError(f"bucket {bid} differs between the "
                               f"program's map and the reference's")
    if program_map.max_devices != ref_map.max_devices:
        raise RuntimeError("max_devices differs")


class _Tester:
    """entry ``crushtester``: crushtool's --test body."""

    def __init__(self, ctx, cmap, args):
        from ceph_tpu.crush.tester import CrushTester
        from ceph_tpu.crush.types import WEIGHT_ONE
        weights = np.full(cmap.max_devices, WEIGHT_ONE, dtype=np.int64)
        self.tester = CrushTester(cmap, weights, batch=args.batch)
        self.mapper = self.tester.mapper

    def sweep(self, rule, num_rep, start, n):
        res = self.tester.test(rule, num_rep, start, start + n - 1)
        return np.asarray(res.device_counts), int(res.bad_mappings)

    def promised(self, rule, num_rep):
        return self.mapper.mapping_path(rule, num_rep)


class _Sharded:
    """entry ``sharded_sweep``: the sweep over a mesh of the cell's
    devices."""

    def __init__(self, ctx, cmap, args):
        from ceph_tpu.crush.mapper import Mapper
        from ceph_tpu.parallel import make_mesh
        self.mapper = Mapper(cmap)
        self.mesh = make_mesh(ctx.devices)

    def sweep(self, rule, num_rep, start, n):
        from ceph_tpu.crush.sharded_sweep import sharded_sweep
        counts, bad = sharded_sweep(self.mesh, self.mapper, rule, start,
                                    n, num_rep)
        return np.asarray(counts), int(bad)   # the read-back is the anchor

    def promised(self, rule, num_rep):
        return self.mapper.mapping_path(rule, num_rep) + "+sharded"


ENTRIES = {"crushtester": _Tester, "sharded_sweep": _Sharded}


def pick_sample(n_sweeps: int, want: int, seed: int) -> list[int]:
    """Indexes of the ``want`` sweeps to compare, drawn from the seed;
    where two or more are wanted the last is one of them (it was in
    flight when the clock ran out), where three or more the first."""
    rng = np.random.default_rng(seed)
    forced = [n_sweeps - 1, 0][:max(0, want - 1)]
    order = forced + [int(i) for i in rng.permutation(n_sweeps)]
    return sorted(list(dict.fromkeys(order))[:max(1, want)])


def compare(ctx, sweeps, sample, ref_counts) -> None:
    """``sweeps``: what the timed path returned, [(start, n, counts,
    bad, path)]; ``ref_counts``: the reference's (counts, bad) for the
    sampled ones. Every number is exact: limit 0."""
    l1 = bad_gap = 0
    for i, (want, want_bad) in zip(sample, ref_counts):
        _start, _n, got, got_bad, _path = sweeps[i]
        if got.shape != want.shape:
            l1 += int(want.sum())
            continue
        l1 += int(np.abs(got.astype(np.int64) - want).sum())
        bad_gap += abs(int(got_bad) - int(want_bad))
    ctx.compared.add("count_l1", l1, 0)
    ctx.compared.add("bad_mappings_gap", bad_gap, 0)
    ctx.compared.add("sweeps_off_path", ctx.obs["sweeps_off_path"], 0)
    ctx.compared.add("device_fallbacks", counters.fallbacks(ctx.delta), 0)


def ref_workers(traffic: dict) -> int:
    """CPU workers of the reference: the traffic file's, else every
    core but one (0: in this process, for a rehearsal)."""
    return int(traffic.get("ref_workers",
                           max(1, (os.cpu_count() or 2) - 1)))


def run(ctx) -> None:
    cfg, tr = ctx.config, ctx.traffic
    n = int(cfg["inputs_per_sweep"])
    rule, num_rep = int(cfg["rule"]), int(cfg["num_rep"])
    workers = ref_workers(tr)
    ref = None
    try:
        with ctx.phase("reference_start"):
            ref = crush_ref.SweepReference(cfg["map"], workers)
        with ctx.phase("map"):
            cmap, args = _build_program_map(cfg["map"])
            _same_map(cmap, crush_ref.build_map(cfg["map"]))
            entry = ENTRIES[tr["entry"]](ctx, cmap, args)
        rng = np.random.default_rng(ctx.seed)
        origin = int(rng.integers(2 * n, 1 << 31))
        with ctx.phase("compile_warmup"):
            for i in (2, 1):
                entry.sweep(rule, num_rep, origin - i * n, n)
        promised = entry.promised(rule, num_rep)
        sweeps, walls = [], []
        plan = ctx.trace_plan()
        tracing = False
        t_open = ctx.open_window()
        deadline = t_open + ctx.seconds
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            if plan and not tracing and ctx.trace_span is None \
                    and now >= t_open + plan[0]:
                ctx.trace_start()
                tracing = True
            start = origin + len(sweeps) * n
            t0 = time.perf_counter()
            with ctx.annotate("sweep"):
                counts, bad = entry.sweep(rule, num_rep, start, n)
            t1 = time.perf_counter()
            walls.append(t1 - t0)
            sweeps.append((start, n, counts, bad,
                           entry.mapper.last_map_path))
            if tracing and t1 >= ctx.t_trace + plan[1]:
                ctx.trace_stop()
                tracing = False
        if tracing:
            ctx.trace_stop()
        # the window closes when the sweep in flight at --seconds has
        # returned: every sweep counts, over all the time they took
        ctx.close_window(t_open)
        ctx.attempted, ctx.failed = len(sweeps), 0
        ctx.values["mappings_s"] = len(sweeps) * n / ctx.window_s
        typical = sorted(walls)[len(walls) // 2]
        ctx.obs.update(
            sweeps=len(sweeps), sweep_s=walls, inputs_per_sweep=n,
            # a stall shows as a sweep far above the median: which, how long
            slow_sweeps=" ".join(f"{i}:{w * 1e3:.0f}ms"
                                 for i, w in enumerate(walls)
                                 if w > 1.2 * typical) or "none",
            promised_path=promised, num_rep=num_rep,
            sweeps_off_path=sum(1 for s in sweeps if s[4] != promised))
        # the program's device state goes before the reference runs
        del entry
        ctx.reduce_trace()
        t0 = time.perf_counter()
        sample = pick_sample(len(sweeps), int(tr.get("check_sweeps", 3)),
                             ctx.seed)
        ref_counts = ref.counts([(sweeps[i][0], n) for i in sample],
                                num_rep)
        compare(ctx, sweeps, sample, ref_counts)
        ctx.obs["sampled_sweeps"] = len(sample)
        ctx.log(f"reference: {len(sample)} sweeps of {n} in "
                f"{time.perf_counter() - t0:.2f}s on {workers} workers")
    finally:
        if ref is not None:
            ref.close()

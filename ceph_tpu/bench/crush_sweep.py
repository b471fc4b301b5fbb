"""North-star #2 benchmark: batched CRUSH mapping rate on TPU.

The `crushtool --test` timing harness scaled to 100M PGs
(ref: src/crush/CrushTester.cc CrushTester::test with --show-statistics;
src/tools/crushtool.cc). The sweep is ONE device program per measurement
(Mapper.sweep: a loop over PG blocks + on-device counts), so the
only host<->device traffic is the final (max_devices,) count readback —
which is also the execution anchor (see ceph_tpu/utils/timing.py).

Methodology: two sweep sizes, rate taken from the SLOPE so the constant
dispatch+readback floor cancels — same discipline as the EC benchmark.

Canonical map: 10k OSDs in a root->rack->host->osd straw2 hierarchy with
a 3-replica chooseleaf rule (BASELINE.md tracked config #3).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

import numpy as np

from ceph_tpu.crush import builder
from ceph_tpu.crush.builder import TYPE_HOST
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.utils.logging import get_logger

log = get_logger("bench")


def canonical_map(n_osds: int = 10240):
    """10k-OSD 3-level map + 3-replica chooseleaf rule (rule 0)."""
    osds_per_host = 16
    n_hosts = n_osds // osds_per_host
    m, root = builder.build_hierarchy(n_hosts, osds_per_host,
                                      n_racks=max(1, n_hosts // 32))
    builder.add_simple_rule(m, root, TYPE_HOST)
    return m


def mixed_weight_map(n_osds: int = 10240):
    """The canonical hierarchy with production-shaped MIXED disk sizes
    (alternating 1T/2T within every host) — breaks every bucket's
    uniform-weight fast path, so this measures the general straw2
    path (VERDICT r3 Missing #2: the headline must not be
    happy-path-only)."""
    from ceph_tpu.crush.types import WEIGHT_ONE
    osds_per_host = 16
    n_hosts = n_osds // osds_per_host
    weights = [WEIGHT_ONE if i % 2 else 2 * WEIGHT_ONE
               for i in range(n_osds)]
    m, root = builder.build_hierarchy(n_hosts, osds_per_host,
                                      n_racks=max(1, n_hosts // 32),
                                      osd_weights=weights)
    builder.add_simple_rule(m, root, TYPE_HOST)
    return m


def choose_args_map(n_osds: int = 10240):
    """Canonical map + a balancer-style choose_args weight-set (per-item
    weights perturbed a few percent) under key 0 — the form
    `ceph balancer` emits via pg-upmap's sibling, crush-compat
    weight-sets (ref: src/crush/CrushWrapper choose_args). Continuous
    per-item perturbation makes every bucket ~size distinct weights, so
    this variant measures the XLA general path."""
    from ceph_tpu.crush.types import ChooseArg
    m = canonical_map(n_osds)
    rng = np.random.default_rng(42)
    args = {}
    for bid, b in m.buckets.items():
        scale = rng.uniform(0.9, 1.1, size=b.size)
        ws = [max(1, int(w * s)) for w, s in zip(b.weights, scale)]
        args[bid] = ChooseArg(weight_set=[ws])
    m.choose_args[0] = args
    return m


def choose_args_quantized_map(n_osds: int = 10240):
    """choose_args_map with each bucket's weight-set snapped to <= 4
    distinct values — the form a TPU-first balancer should emit when it
    uses crush-compat weight-sets at all (our mgr balancer's default is
    pg-upmap, which never touches weights): quantization keeps every
    bucket inside the fused kernel's weight-class draw
    (pallas_mapper MAX_CLASSES), trading a few percent of correction
    resolution for a ~30x mapping-rate difference."""
    from ceph_tpu.crush.types import ChooseArg
    m = canonical_map(n_osds)
    rng = np.random.default_rng(42)
    args = {}
    levels = np.array([0.92, 0.97, 1.03, 1.08])
    for bid, b in m.buckets.items():
        scale = levels[rng.integers(0, 4, size=b.size)]
        ws = [max(1, int(w * s)) for w, s in zip(b.weights, scale)]
        args[bid] = ChooseArg(weight_set=[ws])
    m.choose_args[0] = args
    return m


# variant name -> (map builder, choose_args key)
VARIANT_MAPS = {
    "uniform": (canonical_map, None),
    "mixed_weight": (mixed_weight_map, None),
    "choose_args": (choose_args_map, 0),
    "choose_args_quantized": (choose_args_quantized_map, 0),
}


def _timed_sweep(mapper: Mapper, rule: int, n: int, num_rep: int) -> float:
    """Wall seconds for one aggregated sweep of n PGs, readback-anchored."""
    t0 = time.perf_counter()
    counts, bad = mapper.sweep(rule, 0, n, num_rep)
    np.asarray(counts)  # D2H readback: cannot complete before execution
    return time.perf_counter() - t0


def sweep_rate(n_osds: int = 10240, n_pgs: int = 1 << 22, num_rep: int = 3,
               mapper: Mapper | None = None, rule: int = 0,
               block: int | None = None) -> dict:
    """Measure mappings/s via the two-size slope method."""
    if mapper is None:
        mapper = Mapper(canonical_map(n_osds), block=block)
    # capture the engine the built plan PROMISES before anything runs:
    # a mid-run kernel compile/exec failure silently degrades the
    # Mapper to the XLA path (by design — correctness first), and the
    # PR 4 choose_args regression hid behind exactly that silence
    expected_path = mapper.mapping_path(rule, num_rep)
    # both sizes are whole numbers of the rule's widest block, and
    # DISTINCT ones: every block of both sweeps is then the same
    # program at full fill, so the slope is that program's time a lane
    # (a size off the multiple would end in a narrower tail block, a
    # second program with its own compile in the timed sweep)
    blk = mapper.effective_block(rule, num_rep)
    hi_blocks = max(2, -(-n_pgs // blk))
    lo_blocks = max(1, hi_blocks // 4)
    n_hi = hi_blocks * blk
    n_lo = lo_blocks * blk if lo_blocks < hi_blocks else 0
    # warm/compile (both sizes run the one full-width program; warm so
    # the first-compile cost is excluded from timing)
    _timed_sweep(mapper, rule, n_lo or n_hi, num_rep)
    t_hi = min(_timed_sweep(mapper, rule, n_hi, num_rep) for _ in range(2))
    if n_lo and n_lo < n_hi:
        t_lo = min(_timed_sweep(mapper, rule, n_lo, num_rep)
                   for _ in range(2))
    else:
        t_lo = None
    if t_lo is not None and t_hi > t_lo:
        per_pg = (t_hi - t_lo) / (n_hi - n_lo)
        method = "sweep_two_size_slope_readback"
        overhead = t_lo - n_lo * per_pg
    else:  # single size or noise floor: conservative total
        per_pg = t_hi / n_hi
        method = "sweep_total_readback"
        overhead = 0.0
    rate = 1.0 / per_pg
    import jax
    # which engine ACTUALLY served the sweep (pallas/xla/scalar): a
    # variant silently sliding off the kernel is a visible diff in the
    # bench trajectory, not a mystery slowdown
    actual_path = mapper.last_map_path or expected_path
    out = {
        "metric": "crush_mappings_per_s",
        "mappings_per_s": round(rate, 1),
        "n_pgs": n_hi,
        "n_osds": n_osds,
        "num_rep": num_rep,
        "seconds_per_batch": t_hi,
        "batch": mapper.block,
        "seconds_100M_est": round(1e8 * per_pg + overhead, 3),
        "overhead_s": round(overhead, 4),
        "method": method,
        "path": actual_path,
        "platform": jax.devices()[0].platform,
    }
    # round 15: structural kernel facts per variant — the candidate-
    # batched descent's fused fetch count is a recorded number, so
    # BENCH_r06 can show the measured effect of level-major batching.
    # Attached only when the measured sweep ACTUALLY executed on the
    # kernel path: an XLA/scalar row has no plan to describe, and a
    # mid-run degrade (path_expected_vs_actual above) must not dress
    # its fallback numbers in the batched kernel's geometry.
    if actual_path.split("+", 1)[0].startswith("pallas"):
        info = mapper.kernel_plan_info(rule, num_rep)
        if info is not None:
            out.update(info)
    if actual_path.replace("+sharded", "") != expected_path:
        # Round 16: a quarantine that HEALED before run end is a
        # transient, not a regression — the kernel re-earned its
        # promotion through a bit-exact probe and the plan serves
        # again. Only a mismatch still standing at measurement end
        # (quarantined/permanent, or a pre-quarantine degrade) may
        # reach path_regressions in the driver-parsed tail.
        healed = (mapper.kernel_quarantine_info() is None and
                  mapper.mapping_path(rule, num_rep) == expected_path)
        if healed:
            out["path_transient"] = \
                f"{expected_path}->{actual_path} (healed)"
            log.dout(1, "CRUSH bench transient degrade: the run's "
                        f"last sweep executed {actual_path} but the "
                        f"kernel healed back to {expected_path} "
                        "before run end")
        else:
            # LOUD: the plan promised one engine and the run executed
            # another (kernel compile/exec failure degraded mid-run) —
            # record the diff so the regression cannot hide behind the
            # always-correct fallback's numbers
            out["path_expected_vs_actual"] = \
                f"{expected_path}->{actual_path}"
            log.dout(0, "CRUSH bench path regression: plan promised "
                        f"{expected_path} but the run executed "
                        f"{actual_path}")
    return out


def sweep_rate_variants(n_osds: int = 10240, n_pgs: int = 1 << 21,
                        num_rep: int = 3, block: int | None = None,
                        variants=("uniform", "mixed_weight",
                                  "choose_args")) -> dict:
    """Rates for {uniform, mixed-weight, choose_args} maps — the
    happy-path headline plus the production-shaped slow paths, every
    round (VERDICT r3 Weak #3). The slow variants sweep fewer PGs (they
    are orders of magnitude slower; the slope method cancels the fixed
    overhead either way)."""
    out = {}
    for name in variants:
        build, ca_key = VARIANT_MAPS[name]
        npg = max(1 << 16, n_pgs >> 4) if name == "choose_args" else n_pgs
        mapper = Mapper(build(n_osds), block=block, choose_args=ca_key)
        r = sweep_rate(n_osds, npg, num_rep, mapper=mapper)
        out[name] = {k: r[k] for k in
                     ("mappings_per_s", "n_pgs", "seconds_per_batch",
                      "method", "seconds_100M_est", "path",
                      "path_expected_vs_actual", "path_transient",
                      "fetches_per_sweep", "fetch_amortization",
                      "candidate_batched",
                      "kernel_lanes", "candidate_fold")
                     if k in r}
    return out


def path_regressions(variants: dict) -> list[str]:
    """['choose_args: pallas->xla', ...] for every variant row whose
    built kernel plan silently fell back — bench.py surfaces this in
    the driver-parsed compact summary, so the regression is loud."""
    return [f"{name}: {row['path_expected_vs_actual']}"
            for name, row in sorted(variants.items())
            if isinstance(row, dict)
            and "path_expected_vs_actual" in row]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        prog="crush_sweep", description="batched CRUSH mapping benchmark")
    ap.add_argument("--num-osds", type=int, default=10240)
    ap.add_argument("--num-pgs", type=int, default=1 << 22)
    ap.add_argument("--num-rep", type=int, default=3)
    ap.add_argument("--block", type=int, default=None,
                    help="PGs per device block (default: auto from HBM)")
    ap.add_argument("--variants", action="store_true",
                    help="also measure mixed-weight and choose_args "
                         "map rates (the non-happy paths)")
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="resumable full sweep with per-chunk checkpoint "
                         "(SURVEY.md §5.4); rerun with the same path to "
                         "resume after an interruption")
    ap.add_argument("--chunk", type=int, default=1 << 22,
                    help="PGs per checkpoint chunk")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a jax.profiler trace of the sweep")
    args = ap.parse_args(argv)

    def trace(log_dir):
        """jax.profiler's own context manager (a profiler that will
        not start raises); nothing where no directory was asked for."""
        if not log_dir:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.trace(log_dir)

    if args.checkpoint:
        from ceph_tpu.utils.checkpoint import resumable_sweep
        m = canonical_map(args.num_osds)
        t0 = time.perf_counter()
        with trace(args.profile):
            state, done = resumable_sweep(
                m, 0, args.num_pgs, args.num_rep, args.checkpoint,
                chunk=args.chunk, mapper=Mapper(m, block=args.block))
        res = {
            "metric": "crush_resumable_sweep",
            "done": done,
            "cursor": state.cursor,
            "n_pgs": state.n_total,
            "bad_mappings": state.bad,
            "placements": int(state.counts.sum()),
            "seconds_this_run": round(time.perf_counter() - t0, 3),
        }
    elif args.variants:
        with trace(args.profile):
            res = sweep_rate_variants(args.num_osds, args.num_pgs,
                                      args.num_rep, block=args.block)
    else:
        with trace(args.profile):
            res = sweep_rate(args.num_osds, args.num_pgs, args.num_rep,
                             block=args.block)
    print(json.dumps(res))
    return res


if __name__ == "__main__":
    from ceph_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

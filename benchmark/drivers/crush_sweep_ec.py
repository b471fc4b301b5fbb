"""Traffic kind ``crush_sweep_ec``: ``crushtool --test`` of an erasure
rule, the SAME range swept back to back.

The map is made as an operator makes it, through the program's own
tool: ``crushtool --build`` with the configuration's layers, ``-d`` to
text, the configuration's ``rule_text`` appended, ``-c`` back. The
window then drives ``--test``'s body, ``CrushTester.test(rule, num_rep,
min_x, min_x + inputs_per_sweep - 1)``, on one held tester: one sweep in
flight, the next launched when it returns. Every sweep and every seed
maps the source's own range: an indep block goes round again while any
of its lanes has a position unfilled, so its cost is its range's, and a
seeded origin would make one run dearer than the next by the draw.

The seed draws what is compared, every number exact:

* the per-device counts and the bad mappings (as upstream counts them:
  a short result or a ``CRUSH_ITEM_NONE``) of ``check_sweeps`` of the
  window's sweeps against ``reference/crush_indep_ref.py``;
* ``positions_differing``: after the window, on the same held tester's
  mapper, the result vectors of ``check_positions`` consecutive ids
  inside the range against the reference's, position by position,
  holes included. Counts cannot see two positions swapped; for an EC
  pool the position is the shard id.

The window rules, ``pick_sample``, ``compare``, ``ref_workers`` and
``_same_map`` are ``crush_sweep``'s.
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from drivers import crush_sweep
from reference import crush_indep_ref

# the program's counters of what an indep block did (crush/mapper.PERF);
# a program from before them has none and the readers find nothing
PERF_KEYS = ("indep_blocks", "indep_rounds", "indep_lane_rounds_needed",
             "indep_holes", "sweep_blocks", "sweep_lanes")


def build_program_map(desc: dict, rule_text: str):
    """crushtool --build -o, -d -o, the rule appended, -c -o: the map
    an operator would hand to --test, and the tool's parsed options."""
    from ceph_tpu.bench import crushtool
    from ceph_tpu.encoding import decode_crush_map
    build = ["--build", "--num-osds", str(desc["osds"]),
             "--hosts", str(desc["hosts"]), "--racks", str(desc["racks"]),
             "--alg", desc.get("alg", "straw2")]
    if "batch" in desc:                 # rehearsal only: the tool's default
        build += ["--batch", str(desc["batch"])]
    with tempfile.TemporaryDirectory(prefix="bench_crush_") as tmp:
        built, text, compiled = (os.path.join(tmp, f) for f in
                                 ("built.bin", "map.txt", "map.bin"))
        crushtool.main(build + ["-o", built])
        crushtool.main(["-d", built, "-o", text])
        with open(text, "a") as f:
            f.write("\n" + rule_text)
        crushtool.main(["-c", text, "-o", compiled])
        with open(compiled, "rb") as f:
            cmap = decode_crush_map(f.read())
    return cmap, crushtool.parse_args(build)


def require_bad_mappings_as_upstream() -> None:
    """The source's command is ``--test --show-bad-mappings`` of an
    erasure rule: a program whose tester does not report a mapping
    with a hole as bad (upstream's ``CrushTester::test`` does) cannot
    run this deployment, and the run ends here, at once, before it
    builds anything. One OSD asked for two positions: every mapping
    holds a ``CRUSH_ITEM_NONE``."""
    from ceph_tpu.crush import builder
    from ceph_tpu.crush.tester import CrushTester
    m, root = builder.build_flat(1)
    rid = builder.add_simple_rule(m, root, builder.TYPE_OSD, indep=True)
    bad = CrushTester(m, batch=8).test(rid, 2, 1, 8).bad_mappings
    if bad != 8:
        raise SystemExit(
            f"benchmark: the program's CrushTester reports {bad} bad "
            f"mappings of 8 that each hold a CRUSH_ITEM_NONE; upstream "
            f"reports 8: it cannot run crushtool --test "
            f"--show-bad-mappings of an erasure rule")


def same_rule(cmap, rule: int, steps) -> None:
    """Rule ``rule`` of the program's map has to be the configuration's
    steps, or the window times another rule."""
    got = [(s.op, s.arg1, s.arg2) for s in cmap.rules[rule].steps] \
        if rule in cmap.rules else None
    want = crush_indep_ref.step_codes(steps)
    if got != want:
        raise RuntimeError(f"rule {rule} of the program's map is {got}, "
                           f"the configuration's is {want}")


def perf_snapshot() -> dict:
    from ceph_tpu.crush.mapper import PERF
    d = PERF.dump()
    return {k: d[k] for k in PERF_KEYS if k in d}


def positions_differing(got, want) -> int:
    """Positions at which two blocks of result vectors differ."""
    got = np.asarray(got).astype(np.int64)
    if got.shape != want.shape:
        return int(want.size)
    return int((got != want).sum())


def run(ctx) -> None:
    cfg, tr = ctx.config, ctx.traffic
    n, min_x = int(cfg["inputs_per_sweep"]), int(cfg["min_x"])
    rule, num_rep = int(cfg["rule"]), int(cfg["num_rep"])
    workers = crush_sweep.ref_workers(tr)
    ref = None
    try:
        with ctx.phase("reference_start"):
            ref = crush_indep_ref.IndepReference(
                cfg["map"], cfg["rule_text"], workers)
        with ctx.phase("map"):
            require_bad_mappings_as_upstream()
            cmap, args = build_program_map(cfg["map"], cfg["rule_text"])
            crush_sweep._same_map(cmap, ref.map)
            same_rule(cmap, rule, ref.steps)
            entry = crush_sweep.ENTRIES[tr["entry"]](ctx, cmap, args)
        k = min(n, int(tr.get("check_positions", 65536)))
        with ctx.phase("compile_warmup"):
            for _ in range(2):
                entry.sweep(rule, num_rep, min_x, n)
            # the keep-mappings program of the position check
            np.asarray(entry.mapper.map_pgs(
                rule, np.arange(min_x, min_x + k, dtype=np.uint32), num_rep))
        promised = entry.promised(rule, num_rep)
        sweeps, walls = [], []
        plan = ctx.trace_plan()
        tracing = False
        perf0 = perf_snapshot()
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
            t0 = time.perf_counter()
            with ctx.annotate("sweep"):
                counts, bad = entry.sweep(rule, num_rep, min_x, n)
            t1 = time.perf_counter()
            walls.append(t1 - t0)
            sweeps.append((min_x, n, counts, bad,
                           entry.mapper.last_map_path))
            if tracing and t1 >= ctx.t_trace + plan[1]:
                ctx.trace_stop()
                tracing = False
        if tracing:
            ctx.trace_stop()
        # the window closes when the sweep in flight at --seconds has
        # returned: every sweep counts, over all the time they took
        ctx.close_window(t_open)
        perf1 = perf_snapshot()
        ctx.attempted, ctx.failed = len(sweeps), 0
        ctx.values["mappings_s"] = len(sweeps) * n / ctx.window_s
        typical = sorted(walls)[len(walls) // 2]
        ctx.obs.update(
            sweeps=len(sweeps), sweep_s=walls, inputs_per_sweep=n,
            slow_sweeps=" ".join(f"{i}:{w * 1e3:.0f}ms"
                                 for i, w in enumerate(walls)
                                 if w > 1.2 * typical) or "none",
            promised_path=promised, num_rep=num_rep,
            sweeps_off_path=sum(1 for s in sweeps if s[4] != promised))
        ctx.obs.update({name: perf1[name] - perf0[name] for name in perf1})
        ctx.reduce_trace()
        # the position check, on the held tester's mapper
        rng = np.random.default_rng(ctx.seed)
        at = min_x + int(rng.integers(0, n - k + 1))
        t0 = time.perf_counter()
        got = np.asarray(entry.mapper.map_pgs(
            rule, np.arange(at, at + k, dtype=np.uint32), num_rep))
        ctx.log(f"positions: {k} ids from {at} mapped in "
                f"{time.perf_counter() - t0:.2f}s")
        # the program's device state goes before the reference runs
        del entry
        t0 = time.perf_counter()
        sample = crush_sweep.pick_sample(
            len(sweeps), int(tr.get("check_sweeps", 3)), ctx.seed)
        # every sweep mapped the same range: the reference maps it once
        want, = ref.counts([(min_x, n)], num_rep)
        crush_sweep.compare(ctx, sweeps, sample, [want] * len(sample))
        ctx.compared.add("positions_differing", positions_differing(
            got, ref.vectors(at, k, num_rep)), 0)
        ctx.obs.update(sampled_sweeps=len(sample), positions_from=at,
                       positions_checked=k * num_rep)
        ctx.log(f"reference: one sweep of {n} and {k} vectors in "
                f"{time.perf_counter() - t0:.2f}s on {workers} workers")
    finally:
        if ref is not None:
            ref.close()

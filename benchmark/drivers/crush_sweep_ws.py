"""Traffic kind ``crush_sweep_ws``: ``crushtool --test`` of a map that
carries a compat weight-set, swept back to back.

The map is made as an operator's map comes to the tool. The program's
own ``crushtool --build`` makes the tree; the configuration's weight-set
is installed as ``ceph osd crush weight-set create-compat`` and
``reweight-compat`` install one, through the program's
``create_choose_args`` and ``choose_args_set_item_weights`` (the
single-item ``choose_args_adjust_item_weight`` for every OSD in turn);
the map is written by ``crushtool``'s ``-o`` encoder and read
back by its ``-i`` decoder, so the tester holds what ``ceph osd
getcrushmap -o`` would have given. The window then drives ``--test``'s
body, ``CrushTester.test(rule, num_rep, min_x, min_x + inputs_per_sweep
- 1)``, on one held tester: one sweep in flight, the next launched when
it returns, ``min_x`` stepping by ``inputs_per_sweep`` from an origin
drawn from the seed in [2n, 2^31), so that no two sweeps of a run map
the same ids. The seed also draws what is compared. No flag asks for
the weight-set: the tool honours the map's, as upstream's does.

A program whose tester does NOT honour it cannot run this deployment:
``require_weight_set_honoured`` ends such a run at once, before
anything is built or compiled.

Compared, every number exact (limit 0), against
``reference/crush_ws_ref.py``:

* ``count_l1`` and ``bad_mappings_gap`` over every input of
  ``check_sweeps`` of the window's sweeps (the last, and one drawn from
  the seed), ``sweeps_off_path``, ``device_fallbacks``;
* ``positions_differing``: after the window, on the held tester's
  mapper, the result vectors of ``check_positions`` consecutive ids
  drawn from the seed against the reference's, position by position
  (position 0 is the primary; counts cannot see two positions
  exchanged);
* ``weight_set_differing``: entries of the vectors the held tester's
  map carries that are not the reference's own.

The window rules, ``pick_sample``, ``compare`` and ``ref_workers`` are
``crush_sweep``'s.
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from drivers import crush_sweep
from reference import crush_ws_ref

# the program's counters of what the kernel's flagged-lane fallback did
# (crush/mapper.PERF); a program from before them has none and the
# readers find nothing
PERF_KEYS = ("kernel_flagged_lanes", "kernel_fallback_blocks",
             "kernel_fallback_overflows", "sweep_blocks", "sweep_lanes")


def require_weight_set_honoured() -> None:
    """Two OSDs, and a compat weight-set that gives the second weight
    0: upstream's ``crushtool --test`` maps with that set and places
    nothing there. A program whose tester hands its mapper no
    weight-set tests the unbalanced tree, and the run ends here, at
    once. Asked on the host: nothing is mapped and nothing compiled."""
    from ceph_tpu.crush import builder
    from ceph_tpu.crush.tester import CrushTester
    from ceph_tpu.crush.types import ChooseArg
    m, root = builder.build_flat(2)
    builder.add_simple_rule(m, root, builder.TYPE_OSD)
    m.choose_args[crush_ws_ref.COMPAT] = {
        root: ChooseArg(weight_set=[[0x10000, 0]])}
    served = CrushTester(m, batch=64).mapper.choose_args_key
    if served != crush_ws_ref.COMPAT \
            or not hasattr(builder, "create_choose_args"):
        raise SystemExit(
            f"benchmark: given a map whose compat weight-set weighs an "
            f"OSD 0, the program's CrushTester maps with weight-set "
            f"{served}; upstream's crushtool --test maps with the compat "
            f"set and places nothing there: it does not honour the map's "
            f"choose_args and cannot run this deployment")


def build_program_map(desc: dict, ws: dict, osd_w):
    """crushtool --build, the weight-set installed as upstream installs
    one, -o, -i: the decoded map and the tool's parsed options."""
    from ceph_tpu.bench import crushtool
    from ceph_tpu.crush import builder
    from ceph_tpu.encoding import decode_crush_map, encode_crush_map
    key, positions = int(ws["id"]), int(ws["positions"])
    args = crush_sweep._build_program_map(desc)[1]
    cmap = crushtool.build_map(args)
    builder.create_choose_args(cmap, key, positions)
    builder.choose_args_set_item_weights(
        cmap, key, {osd: [w] * positions for osd, w in enumerate(osd_w)})
    with tempfile.TemporaryDirectory(prefix="bench_crush_") as tmp:
        got, kept = (os.path.join(tmp, f) for f in ("got.bin", "kept.bin"))
        with open(got, "wb") as f:          # ceph osd getcrushmap -o
            f.write(encode_crush_map(cmap))
        crushtool.main(["-i", got, "-o", kept])     # the tool's own pass
        with open(kept, "rb") as f:
            cmap = decode_crush_map(f.read())
    return cmap, args


def same_map(cmap, ref) -> None:
    """The tree has to be the reference's (``crush_sweep._same_map``),
    and so has every entry of the weight-set, or the window times
    another deployment."""
    crush_sweep._same_map(cmap, ref.base)
    key = int(ref.ws["id"])
    if set(cmap.choose_args) != {key}:
        raise RuntimeError(f"the program's map carries the weight-sets "
                           f"{sorted(cmap.choose_args)}, the "
                           f"configuration's is {key}")
    off = crush_ws_ref.vectors_differing(cmap.choose_args[key],
                                         ref.weight_set)
    if off:
        raise RuntimeError(f"{off} entries of the program's weight-set "
                           f"are not the configuration's")


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


def positions_block(ctx, k: int) -> int:
    """First id of the block of vectors a run with this seed compares."""
    rng = np.random.default_rng([ctx.seed, 1])
    return int(rng.integers(0, (1 << 32) - k))


def origin_of(ctx, n: int) -> int:
    """First id of a run's first sweep, drawn from the seed as
    ``crush_sweep`` draws it: the warm-up's two sweeps lie below it."""
    return int(np.random.default_rng(ctx.seed).integers(2 * n, 1 << 31))


def run(ctx) -> None:
    cfg, tr = ctx.config, ctx.traffic
    n = int(cfg["inputs_per_sweep"])
    rule, num_rep = int(cfg["rule"]), int(cfg["num_rep"])
    key = int(cfg["weight_set"]["id"])
    workers = crush_sweep.ref_workers(tr)
    ref = None
    try:
        with ctx.phase("probe"):
            require_weight_set_honoured()
        with ctx.phase("reference_start"):
            ref = crush_ws_ref.WeightSetReference(
                cfg["map"], cfg["weight_set"], workers)
        with ctx.phase("map"):
            cmap, args = build_program_map(cfg["map"], cfg["weight_set"],
                                           ref.osd_weights)
            same_map(cmap, ref)
            entry = crush_sweep.ENTRIES[tr["entry"]](ctx, cmap, args)
            if entry.tester.choose_args_key != key:
                raise RuntimeError(
                    f"the tester serves weight-set "
                    f"{entry.tester.choose_args_key}, the map's is {key}")
        origin = origin_of(ctx, n)
        k = min(n, int(tr.get("check_positions", 65536)))
        at = positions_block(ctx, k)
        with ctx.phase("compile_warmup"):
            for i in (2, 1):
                entry.sweep(rule, num_rep, origin - i * n, n)
            # the keep-mappings program of the position check
            np.asarray(entry.mapper.map_pgs(
                rule, np.arange(k, dtype=np.uint32), num_rep))
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
        perf1 = perf_snapshot()
        ctx.attempted, ctx.failed = len(sweeps), 0
        ctx.values["mappings_s"] = len(sweeps) * n / ctx.window_s
        typical = sorted(walls)[len(walls) // 2]
        ctx.obs.update(
            sweeps=len(sweeps), sweep_s=walls, inputs_per_sweep=n,
            slow_sweeps=" ".join(f"{i}:{w * 1e3:.0f}ms"
                                 for i, w in enumerate(walls)
                                 if w > 1.2 * typical) or "none",
            sweep_ms_min_p50_max=" ".join(
                f"{w * 1e3:.1f}" for w in (min(walls), typical, max(walls))),
            promised_path=promised, num_rep=num_rep,
            choose_args=entry.tester.choose_args_key,
            sweeps_off_path=sum(1 for s in sweeps if s[4] != promised))
        ctx.obs.update({name: perf1[name] - perf0[name] for name in perf1})
        ctx.reduce_trace()
        # the position check and the weight-set, on the held tester
        t0 = time.perf_counter()
        got = np.asarray(entry.mapper.map_pgs(
            rule, (np.arange(k, dtype=np.uint64) + np.uint64(at))
            .astype(np.uint32), num_rep))
        ctx.log(f"positions: {k} ids from {at} mapped in "
                f"{time.perf_counter() - t0:.2f}s")
        ws_off = crush_ws_ref.vectors_differing(
            entry.tester.map.choose_args.get(key), ref.weight_set)
        # the program's device state goes before the reference runs
        del entry
        t0 = time.perf_counter()
        sample = crush_sweep.pick_sample(
            len(sweeps), int(tr.get("check_sweeps", 2)), ctx.seed)
        ref_counts = ref.counts([(sweeps[i][0], n) for i in sample],
                                num_rep)
        crush_sweep.compare(ctx, sweeps, sample, ref_counts)
        ctx.compared.add("positions_differing", positions_differing(
            got, ref.vectors(at, k, num_rep)), 0)
        ctx.compared.add("weight_set_differing", ws_off, 0)
        ctx.obs.update(sampled_sweeps=len(sample), positions_from=at,
                       positions_checked=k * num_rep)
        ctx.log(f"reference: {len(sample)} sweeps of {n} and {k} vectors "
                f"in {time.perf_counter() - t0:.2f}s on {workers} workers")
    finally:
        if ref is not None:
            ref.close()

"""Traffic kind ``crush_sweep_classes``: ``crushtool --test`` of a rule
that places by device class, the SAME range swept back to back.

The map is made as a live cluster's map comes to the tool: the
program's ``crushtool --build`` makes the tree and ``-d`` its text;
every ``device N osd.N`` line gets the OSD's ``class``, and every
bucket its two shadow ids as ``id <n> class <c>`` lines, as ``ceph osd
getcrushmap`` then ``crushtool -d`` prints them (the ids by the
configuration's rule, from ``reference/crush_class_ref.py``); the
configuration's ``rule_text`` is appended, ``-c`` compiles it and the
binary is decoded. The window then drives ``--test``'s body,
``CrushTester.test(rule, num_rep, min_x, min_x + inputs_per_sweep -
1)``, on one held tester: one sweep in flight, the next launched when
it returns. Every sweep and every seed maps the same range, as
``crush_sweep_ec`` does: a firstn slot's retry loop costs its unluckiest
lane, so a seeded origin would make one run dearer than the next.

A program whose compiler does not give a shadow the id its line states
places on other shadow ids: ``require_class_ids_honoured`` ends such a
run at once, before anything is built or compiled.

Compared, every number exact (limit 0), against the reference: the
counts and bad mappings of ``check_sweeps`` sweeps drawn from the seed
(``count_l1``, ``bad_mappings_gap``), ``sweeps_off_path``,
``device_fallbacks`` (``crush_sweep.compare``); ``positions_differing``
over ``check_positions`` consecutive vectors drawn from the seed, on
the held tester's mapper (position 0 is the ssd primary);
``shadow_ids_differing``: shadows of the held tester's map whose id or
items are not the configuration's.

``pick_sample``, ``compare``, ``ref_workers``, ``_same_map`` and
``ENTRIES`` are ``crush_sweep``'s, ``positions_differing``
``crush_sweep_ec``'s; ``sweep_window`` is the window loop the other
sweep drivers each write out in their ``run``.
"""

from __future__ import annotations

import os
import re
import tempfile
import time
import types

import numpy as np

from drivers import crush_sweep
from drivers.crush_sweep_ec import positions_differing
from reference import crush_class_ref

# the program's counters of what a firstn block on the rule VM did
# (crush/mapper.PERF); a program from before them has none and the
# readers find nothing
PERF_KEYS = ("firstn_slots", "firstn_loop_lanes", "firstn_loop_rounds",
             "sweep_blocks", "sweep_lanes")

PROBE = """device 0 osd.0 class hdd
device 1 osd.1 class hdd
type 0 osd
type 1 host
type 10 root
host h {
\tid -1
\tid -7 class hdd
\talg straw2
\thash 0
\titem osd.0 weight 1.000
\titem osd.1 weight 1.000
}
root r {
\tid -2
\talg straw2
\thash 0
\titem h weight 2.000
}
rule r {
\tid 0
\ttype replicated
\tstep take r class hdd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
"""


def require_class_ids_honoured() -> None:
    """Two buckets, and a line that gives the host's hdd shadow the id
    -7: ``crushtool -c`` builds the shadow under that id, as upstream's
    does. A program that makes its own ids places the configuration's
    rule on other shadows, and the run ends here, at once. Compiled on
    the host: nothing is mapped and nothing compiled."""
    from ceph_tpu.crush.compiler import compile_crushmap
    m = compile_crushmap(PROBE)
    got = {name: bid for bid, name in m.bucket_names.items()}.get("h~hdd")
    if got != -7:
        raise SystemExit(
            f"benchmark: given a map whose text states 'id -7 class hdd' "
            f"for host h, the program's crushtool -c gives h~hdd the id "
            f"{got}; upstream's gives it -7: it does not keep the map's "
            f"shadow ids and cannot run this deployment")


def class_text(text: str, klass, shadows) -> str:
    """A ``crushtool -d`` text with every OSD's class and every
    bucket's shadow ids written in, as a live cluster's map prints."""
    ids = {}
    for (bid, c), b in shadows.items():
        ids.setdefault(bid, []).append((c, b.id))
    out = []
    for line in text.splitlines():
        dev = re.match(r"device (\d+) osd\.\d+$", line)
        if dev:
            line += f" class {klass[int(dev.group(1))]}"
        out.append(line)
        bid = re.match(r"\tid (-\d+)$", line)
        if bid:
            out += [f"\tid {sid} class {c}"
                    for c, sid in sorted(ids.get(int(bid.group(1)), ()))]
    return "\n".join(out) + "\n"


def build_program_map(desc: dict, rule_text: str, ref):
    """crushtool --build -o, -d -o, the classes and shadow ids written
    in, the rule appended, -c -o, the binary decoded: the map and the
    tool's parsed options."""
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
        with open(text) as f:
            edited = class_text(f.read(), ref.klass, ref.shadows)
        with open(text, "w") as f:
            f.write(edited + "\n" + rule_text)
        crushtool.main(["-c", text, "-o", compiled])
        with open(compiled, "rb") as f:
            cmap = decode_crush_map(f.read())
    return cmap, crushtool.parse_args(build)


def same_tree_and_rule(cmap, ref, rule: int) -> None:
    """The tree (shadows aside) has to be the reference's, and rule
    ``rule`` its steps with each take naming the same root and class,
    or the window times another deployment. The shadows' ids are
    compared after the window (``shadow_ids_differing``)."""
    base = {bid for bid, name in cmap.bucket_names.items() if "~" not in name}
    crush_sweep._same_map(types.SimpleNamespace(
        buckets={b: cmap.buckets[b] for b in base},
        max_devices=cmap.max_devices), ref.base)

    def named(m_names, steps):
        return [(op, m_names.get(a1, a1) if op == 1 else a1, a2)
                for op, a1, a2 in steps]
    ref_names = {b.id: f"root~{c}" for (bid, c), b in ref.shadows.items()
                 if bid == min(ref.base.buckets)}
    ref_names[min(ref.base.buckets)] = "root"
    got = named(cmap.bucket_names, [(s.op, s.arg1, s.arg2) for s in
                                    cmap.rules[rule].steps]) \
        if rule in cmap.rules else None
    want = named(ref_names, crush_class_ref.step_codes(ref.steps))
    if got != want:
        raise RuntimeError(f"rule {rule} of the program's map is {got}, "
                           f"the configuration's is {want}")


def perf_snapshot() -> dict:
    from ceph_tpu.crush.mapper import PERF
    d = PERF.dump()
    return {k: d[k] for k in PERF_KEYS if k in d}


def sweep_window(ctx, entry, rule: int, num_rep: int, start_of, n: int):
    """Back-to-back sweeps for ``ctx.seconds``, the traced stretch
    bracketed where the run is traced: ``[(start, n, counts, bad,
    path)]`` and each call's wall. The window closes when the sweep in
    flight at ``--seconds`` has returned: every sweep counts, over all
    the time they took."""
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
        start = start_of(len(sweeps))
        t0 = time.perf_counter()
        with ctx.annotate("sweep"):
            counts, bad = entry.sweep(rule, num_rep, start, n)
        t1 = time.perf_counter()
        walls.append(t1 - t0)
        sweeps.append((start, n, counts, bad, entry.mapper.last_map_path))
        if tracing and t1 >= ctx.t_trace + plan[1]:
            ctx.trace_stop()
            tracing = False
    if tracing:
        ctx.trace_stop()
    ctx.close_window(t_open)
    return sweeps, walls


def run(ctx) -> None:
    cfg, tr = ctx.config, ctx.traffic
    n, min_x = int(cfg["inputs_per_sweep"]), int(cfg["min_x"])
    rule, num_rep = int(cfg["rule"]), int(cfg["num_rep"])
    workers = crush_sweep.ref_workers(tr)
    ref = None
    try:
        with ctx.phase("probe"):
            require_class_ids_honoured()
        with ctx.phase("reference_start"):
            ref = crush_class_ref.ClassReference(
                cfg["map"], cfg["classes"], cfg["rule_text"], workers)
        with ctx.phase("map"):
            cmap, args = build_program_map(cfg["map"], cfg["rule_text"], ref)
            same_tree_and_rule(cmap, ref, rule)
            entry = crush_sweep.ENTRIES[tr["entry"]](ctx, cmap, args)
        k = min(n, int(tr.get("check_positions", 65536)))
        at = min_x + int(np.random.default_rng(ctx.seed)
                         .integers(0, n - k + 1))
        with ctx.phase("compile_warmup"):
            for _ in range(2):
                entry.sweep(rule, num_rep, min_x, n)
            # the keep-mappings program of the position check
            np.asarray(entry.mapper.map_pgs(
                rule, np.arange(min_x, min_x + k, dtype=np.uint32), num_rep))
        promised = entry.promised(rule, num_rep)
        perf0 = perf_snapshot()
        sweeps, walls = sweep_window(ctx, entry, rule, num_rep,
                                     lambda _i: min_x, n)
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
            takes=entry.mapper.takes(rule),
            sweeps_off_path=sum(1 for s in sweeps if s[4] != promised))
        ctx.obs.update({name: perf1[name] - perf0[name] for name in perf1})
        ctx.reduce_trace()
        # the position check and the shadows, on the held tester
        t0 = time.perf_counter()
        got = np.asarray(entry.mapper.map_pgs(
            rule, np.arange(at, at + k, dtype=np.uint32), num_rep))
        ctx.log(f"positions: {k} ids from {at} mapped in "
                f"{time.perf_counter() - t0:.2f}s")
        held = entry.tester.map
        shadows_off = crush_class_ref.shadows_differing(
            held.bucket_names, held.buckets, ref.shadows)
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
        ctx.compared.add("shadow_ids_differing", shadows_off, 0)
        ctx.obs.update(sampled_sweeps=len(sample), positions_from=at,
                       positions_checked=k * num_rep,
                       shadows_checked=len(ref.shadows))
        ctx.log(f"reference: one sweep of {n} and {k} vectors in "
                f"{time.perf_counter() - t0:.2f}s on {workers} workers")
    finally:
        if ref is not None:
            ref.close()

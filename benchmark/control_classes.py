#!/usr/bin/env python3
"""The controls of traffic kind ``crush_sweep_classes``, as ``control.py``
has them for the other kinds: the plain reference put in the program's
place with one guarantee broken, run through the comparison a run
makes. Each has to come out NOT correct; a benchmark run never runs it.

    python benchmark/control_classes.py --workload <cell> --seeds 1,2,3 [--rehearsal]

* ``shadow_ids_regenerated``: the map's ``id <n> class <c>`` lines
  ignored and the shadows made in the order the rule takes its classes
  (ssd, then hdd): what a ``crushtool -c`` that does not keep them
  answers (this program's, before the cell).
* ``emit_not_truncated``: EMIT keeps every item its block chose, the
  second block's third HDD among them.

The run's sweep (the range every sweep maps) and its block of vectors
are mapped each way on CPU workers. Prints one line per seed and
control, and exits 0 only if every one came out not correct.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import numpy as np                                   # noqa: E402

import control                                       # noqa: E402
from reference import crush_class_ref                # noqa: E402

# control -> (the reference's map, whether EMIT truncates)
CONTROLS = {"shadow_ids_regenerated": ("regenerated", True),
            "emit_not_truncated": ("cfg", False)}


def reference(ctx, driver, workers=None):
    """The cell's reference with the regenerated shadows beside its
    own."""
    cfg = ctx.config
    return crush_class_ref.ClassReference(
        cfg["map"], cfg["classes"], cfg["rule_text"],
        driver.crush_sweep.ref_workers(ctx.traffic)
        if workers is None else workers,
        {"regenerated": list(reversed(cfg["classes"]["order"]))})


def sound_answer(ctx, ref) -> tuple:
    """What a run with this seed compares, answered soundly: (the
    sweep's first id, its length, the first id of the block of vectors,
    its length, the sweep's exact (counts, bad), the block's vectors)."""
    cfg = ctx.config
    n, min_x = int(cfg["inputs_per_sweep"]), int(cfg["min_x"])
    k = min(n, int(ctx.traffic.get("check_positions", 65536)))
    at = min_x + int(np.random.default_rng(ctx.seed).integers(0, n - k + 1))
    exact, = ref.counts([(min_x, n)], int(cfg["num_rep"]))
    return min_x, n, at, k, exact, ref.vectors(at, k, int(cfg["num_rep"]))


def control_classes(ctx, driver, ref, kind: str, sound: tuple) -> None:
    num_rep = int(ctx.config["num_rep"])
    which, truncate = CONTROLS[kind]
    start, n, at, k, exact, want = sound
    (got, got_bad), = ref.counts([(start, n)], num_rep, which=which,
                                 truncate=truncate)
    ctx.obs["sweeps_off_path"] = 0
    driver.crush_sweep.compare(ctx, [(start, n, got, got_bad, "control")],
                               [0], [exact])
    ctx.compared.add("positions_differing", driver.positions_differing(
        ref.vectors(at, k, num_rep, which=which, truncate=truncate),
        want), 0)
    # the shadows a tester that answered so would have held, named as
    # the program names them
    held = ref.shadows if which == "cfg" else crush_class_ref.shadow_trees(
        ref.base, ref.klass, reversed(ctx.config["classes"]["order"]))
    names = {bid: f"b{-bid}" for bid in ref.base.buckets}
    names.update({b.id: f"b{-bid}~{c}" for (bid, c), b in held.items()})
    ctx.compared.add("shadow_ids_differing",
                     crush_class_ref.shadows_differing(
                         names, {b.id: b for b in held.values()},
                         ref.shadows), 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    ref, as_said = None, True
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            sound = None
            for kind in CONTROLS:
                ctx, driver = control._context(args.workload, seed,
                                               args.rehearsal)
                if ref is None:
                    ref = reference(ctx, driver)
                sound = sound or sound_answer(ctx, ref)
                control_classes(ctx, driver, ref, kind, sound)
                print(json.dumps({"workload": args.workload, "seed": seed,
                                  "control": kind,
                                  "control_correct": ctx.compared.ok,
                                  "compared": ctx.compared.rows}),
                      flush=True)
                as_said &= not ctx.compared.ok
    finally:
        if ref is not None:
            ref.close()
    return 0 if as_said else 1


if __name__ == "__main__":
    sys.exit(main())

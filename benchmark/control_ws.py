#!/usr/bin/env python3
"""The controls of traffic kind ``crush_sweep_ws``, as ``control.py``
has them for the other kinds: the plain reference put in the program's
place with one guarantee broken, run through the comparison a run
makes. Each has to come out NOT correct; a benchmark run never runs it.

    python benchmark/control_ws.py --workload <cell> --seeds 1,2,3 [--rehearsal]

* ``weight_set_ignored``: the tree mapped as built, the weight-set left
  out: what a ``crushtool --test`` that does not honour ``choose_args``
  answers (this program's, before the cell).
* ``weight_set_quantized``: every vector snapped to four weight classes
  by the program's ``builder.quantize_choose_args``, what this
  program's mgr installs in ``crush-compat`` mode: an approximate
  answer where the configuration states an exact one.
* ``float32_ln``: straw2's fixed-point ``crush_ln`` replaced by a
  float32 log2, the nearest precision below the 48-bit table.

One sweep of the cell's own size, one of the first 64 of a run's
sequence drawn from the seed, and the run's block of vectors, are
mapped each way on CPU workers. Prints one line per seed and control, and exits 0 only if
every one came out not correct with ``count_l1`` above 0.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import numpy as np                                   # noqa: E402

import control                                       # noqa: E402
from reference import crush_ws_ref                   # noqa: E402

CONTROLS = {"weight_set_ignored": ("none", "exact"),
            "weight_set_quantized": ("quantized", "exact"),
            "float32_ln": ("ws", "float32")}


def quantized_vectors(driver, cfg: dict, osd_w) -> dict:
    """The configuration's weight-set after the program's own
    ``quantize_choose_args``: bucket id -> vector."""
    from ceph_tpu.crush import builder
    ws = cfg["weight_set"]
    cmap, _args = driver.build_program_map(cfg["map"], ws, osd_w)
    builder.quantize_choose_args(cmap, key=int(ws["id"]))
    return {bid: [int(w) for w in arg.weight_set[0]]
            for bid, arg in cmap.choose_args[int(ws["id"])].items()}


def sound_answer(ctx, driver, ref) -> tuple:
    """What a run with this seed compares, answered soundly: (first id
    of a sweep of the run's sequence, first id of the block of vectors,
    its length, the sweep's exact (counts, bad), the block's exact
    vectors)."""
    cfg = ctx.config
    n, num_rep = int(cfg["inputs_per_sweep"]), int(cfg["num_rep"])
    start = driver.origin_of(ctx, n) \
        + n * int(np.random.default_rng([ctx.seed, 2]).integers(0, 64))
    k = min(n, int(ctx.traffic.get("check_positions", 65536)))
    at = driver.positions_block(ctx, k)
    exact, = ref.counts([(start, n)], num_rep)
    return start, at, k, exact, ref.vectors(at, k, num_rep)


def control_ws(ctx, driver, ref, kind: str, sound: tuple) -> None:
    cfg = ctx.config
    n, num_rep = int(cfg["inputs_per_sweep"]), int(cfg["num_rep"])
    which, ln = CONTROLS[kind]
    start, at, k, exact, want = sound
    (got, got_bad), = ref.counts([(start, n)], num_rep, ln, which)
    ctx.obs["sweeps_off_path"] = 0
    driver.crush_sweep.compare(ctx, [(start, n, got, got_bad, "control")],
                               [0], [exact])
    ctx.compared.add("positions_differing", driver.positions_differing(
        ref.vectors(at, k, num_rep, ln, which), want), 0)
    # what a tester that answered so would have held: bucket id -> vector
    held = {"ws": ref.weight_set, "none": None, **ref.extra}[which]
    if held is not None:
        held = {bid: types.SimpleNamespace(weight_set=[v], ids=None)
                for bid, v in held.items()}
    ctx.compared.add("weight_set_differing",
                     crush_ws_ref.vectors_differing(held, ref.weight_set), 0)


def reference(ctx, driver, workers=None):
    """The cell's reference with the quantized map beside its own."""
    cfg = ctx.config
    osd_w = crush_ws_ref.osd_weights(cfg["map"], cfg["weight_set"])
    extra = {"quantized": quantized_vectors(driver, cfg, osd_w)}
    return crush_ws_ref.WeightSetReference(
        cfg["map"], cfg["weight_set"],
        driver.crush_sweep.ref_workers(ctx.traffic)
        if workers is None else workers, extra)


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
                sound = sound or sound_answer(ctx, driver, ref)
                control_ws(ctx, driver, ref, kind, sound)
                rows = ctx.compared.rows
                print(json.dumps({"workload": args.workload, "seed": seed,
                                  "control": kind,
                                  "control_correct": ctx.compared.ok,
                                  "compared": rows}), flush=True)
                as_said &= not ctx.compared.ok \
                    and rows["count_l1"]["value"] > 0
    finally:
        if ref is not None:
            ref.close()
    return 0 if as_said else 1


if __name__ == "__main__":
    sys.exit(main())

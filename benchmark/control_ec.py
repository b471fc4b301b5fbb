#!/usr/bin/env python3
"""The controls of traffic kind ``crush_sweep_ec``, as ``control.py``
has them for the other kinds: the plain reference put in the program's
place with one guarantee broken, run through the comparison a run
makes. Each has to come out NOT correct; a benchmark run never runs it.

    python benchmark/control_ec.py --workload <cell> --seeds 1,2,3 [--rehearsal]

* ``float32_ln``: straw2's fixed-point ``crush_ln`` replaced by a
  float32 log2, the nearest precision below the 48-bit table. The whole
  range of the cell is mapped both ways on CPU workers: the counts
  differ (``count_l1``), and so do the compared vectors.
* ``positions_swapped``: every result vector with its first two
  positions exchanged -- two shards of every PG on each other's OSD.
  The counts are the same, device for device (``count_l1`` 0): only
  ``positions_differing`` sees it.

Every sweep of the cell maps one range, so the reference maps it once
for all seeds; the seed draws the block of vectors, as in a run. Prints
one line per seed and control, and exits 0 only if every one came out
not correct with the numbers above.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import numpy as np                                   # noqa: E402

import control                                       # noqa: E402
from reference import crush_indep_ref                # noqa: E402


def positions_block(ctx) -> tuple[int, int]:
    """(first id, length) of the block of vectors a run with this seed
    compares: the driver's own draw."""
    n, min_x = int(ctx.config["inputs_per_sweep"]), int(ctx.config["min_x"])
    k = min(n, int(ctx.traffic.get("check_positions", 65536)))
    rng = np.random.default_rng(ctx.seed)
    return min_x + int(rng.integers(0, n - k + 1)), k


def control_ec(ctx, driver, ref, kind: str, exact, float32=None) -> None:
    """``exact`` / ``float32``: the reference's (counts, bad) of the
    cell's range, the second for ``float32_ln`` only."""
    cfg = ctx.config
    n, min_x = int(cfg["inputs_per_sweep"]), int(cfg["min_x"])
    num_rep = int(cfg["num_rep"])
    at, k = positions_block(ctx)
    want = ref.vectors(at, k, num_rep)
    if kind == "float32_ln":
        (got, got_bad) = float32
        vectors = ref.vectors(at, k, num_rep, "float32")
    else:
        (got, got_bad) = exact
        vectors = want.copy()
        vectors[:, [0, 1]] = vectors[:, [1, 0]]
    ctx.obs["sweeps_off_path"] = 0
    driver.crush_sweep.compare(ctx, [(min_x, n, got, got_bad, "control")],
                               [0], [exact])
    ctx.compared.add("positions_differing",
                     driver.positions_differing(vectors, want), 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    ref, as_said = None, True
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            for kind in ("float32_ln", "positions_swapped"):
                ctx, driver = control._context(args.workload, seed,
                                               args.rehearsal)
                if ref is None:
                    cfg = ctx.config
                    ref = crush_indep_ref.IndepReference(
                        cfg["map"], cfg["rule_text"],
                        driver.crush_sweep.ref_workers(ctx.traffic))
                    sweep = [(int(cfg["min_x"]),
                              int(cfg["inputs_per_sweep"]))]
                    exact, = ref.counts(sweep, int(cfg["num_rep"]))
                    float32, = ref.counts(sweep, int(cfg["num_rep"]),
                                          "float32")
                control_ec(ctx, driver, ref, kind, exact, float32)
                rows = ctx.compared.rows
                print(json.dumps({"workload": args.workload, "seed": seed,
                                  "control": kind,
                                  "control_correct": ctx.compared.ok,
                                  "compared": rows}), flush=True)
                l1 = rows["count_l1"]["value"]
                as_said &= not ctx.compared.ok \
                    and rows["positions_differing"]["value"] > 0 \
                    and (l1 > 0 if kind == "float32_ln" else l1 == 0)
    finally:
        if ref is not None:
            ref.close()
    return 0 if as_said else 1


if __name__ == "__main__":
    sys.exit(main())

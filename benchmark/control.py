#!/usr/bin/env python3
"""The control of each kind of traffic: the plain reference put in the
program's place with one guarantee of the configuration broken, run
through the same comparison a run makes. It has to come out as NOT
correct; a benchmark run never runs it.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 [--rehearsal]

* ``crush_sweep``: straw2's fixed-point ``crush_ln`` replaced by a
  float32 log2 -- the nearest precision below the 48-bit table, the step
  a faster kernel would be tempted by. A whole sweep of the cell's own
  size is mapped both ways on CPU workers; the counts differ.
* ``rados_bench`` write: the write is acknowledged when k+m-1 shards
  are in their stores and the last parity shard lands later ("fewer
  acknowledgements"): the shards counted at the ack fall short.
* ``rados_bench`` degraded read: the reads of objects that lack a data
  shard come back without the decode (the lost chunk of every stripe
  zero): the bytes differ.

Prints one line per seed with every number beside its limit, and exits
0 only if every seed came out not correct.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import numpy as np                                   # noqa: E402

from harness import runner                           # noqa: E402
from reference import crush_ref, rs_ref              # noqa: E402


class _Args:
    def __init__(self, seed, rehearsal):
        self.seed, self.seconds, self.trace = seed, 1.0, 0
        self.rehearsal = rehearsal


def _context(workload: str, seed: int, rehearsal: bool):
    spec, cell, config, traffic = runner.load_cell(workload, rehearsal)
    ctx = runner.Context(spec, cell, config, traffic,
                         _Args(seed, rehearsal), 0.0)
    return ctx, runner.load_driver(traffic)


def control_crush(ctx, driver, ref) -> None:
    cfg = ctx.config
    n, num_rep = int(cfg["inputs_per_sweep"]), int(cfg["num_rep"])
    rng = np.random.default_rng(ctx.seed)
    start = int(rng.integers(2 * n, 1 << 31))
    (want, want_bad), = ref.counts([(start, n)], num_rep, "exact")
    (got, got_bad), = ref.counts([(start, n)], num_rep, "float32")
    ctx.obs["sweeps_off_path"] = 0
    driver.compare(ctx, [(start, n, got, got_bad, "control")], [0],
                   [(want, want_bad)])


def control_rados(ctx, driver) -> None:
    p = driver.Params(ctx.config, ctx.traffic)
    p.make_payloads(ctx.seed)
    n = int(ctx.traffic.get("check_objects", 32))
    objects = list(range(n))
    answers, at_ack, kept = [], {}, []
    lost = 2 if ctx.traffic.get("degraded") else None
    for i in objects:
        data = p.payloads.get(i)
        shards = rs_ref.shards(data, p.k, p.m, p.unit)
        stored = {pos: s for pos, s in enumerate(shards) if pos != lost}
        read = data
        if p.mode == "write":
            # acknowledged with the last parity shard still on its way
            at_ack[i] = p.k + p.m - 1
        else:
            # the decode skipped: the lost chunk of each stripe is zero
            parts = [shards[j] if j != lost else bytes(len(shards[j]))
                     for j in range(p.k)]
            read = rs_ref.assemble(parts, p.k, p.unit, p.size)
            kept.append((i, read))
        answers.append({"object": i, "stored": stored, "lost": lost,
                        "read": read})
    driver.compare(ctx, p, answers, at_ack, kept)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ref, failed_all = None, True
    try:
        for seed in seeds:
            ctx, driver = _context(args.workload, seed, args.rehearsal)
            if ctx.traffic["driver"] == "crush_sweep":
                if ref is None:
                    ref = crush_ref.SweepReference(
                        ctx.config["map"], driver.ref_workers(ctx.traffic))
                control_crush(ctx, driver, ref)
            else:
                control_rados(ctx, driver)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control_correct": ctx.compared.ok,
                              "compared": ctx.compared.rows}), flush=True)
            failed_all &= not ctx.compared.ok
    finally:
        if ref is not None:
            ref.close()
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())

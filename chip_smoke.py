#!/usr/bin/env python3
"""First light on the chip: drive the served EC path, placement and the
kernels once through the entry points a user calls, check every result
against the repo's own references, and say which engine served.

    python chip_smoke.py             # one chip: served, placement, kernels
    python chip_smoke.py --chips 4   # four chips: the sharded phase only
    python chip_smoke.py --rehearsal # same phases, tiny sizes (still TPU)

One process, no child that touches JAX (a chip belongs to one process;
the reference-mapping workers are pinned to the CPU and never run a JAX
op). Every phase prints one JSON line; the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and the exit code is 0 only if every phase passed on a TPU. There is no
CPU branch: tests/test_chip_smoke.py swaps ``EXPECT`` to rehearse the
phase functions off the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

# What the chip must show. The engines are asserted, not just printed:
# every device failure on these paths is caught and served from the
# host (the aggregators' degrade ladders, Mapper._disable_kernel), so a
# byte-compare alone would pass with the chip's kernels dead.
EXPECT = {"platform": "tpu", "ec_backend": "pallas", "crush_path": "pallas"}

EC83 = ["plugin=jax", "technique=reed_sol_van", "k=8", "m=3",
        "crush-failure-domain=osd"]
EC42 = ["plugin=jax", "technique=reed_sol_van", "k=4", "m=2",
        "crush-failure-domain=osd"]


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of one run. Shapes (object size, k/m, stripe unit, map
    width) are the same in both; only counts differ."""
    object_size: int = 4 << 20      # rados bench default
    in_flight: int = 16             # rados bench default (-t 16)
    objects_83: int = 64
    objects_42: int = 16
    degraded_reads: int = 16
    n_osds: int = 10240             # tracked config #3
    hosts: int = 640
    racks: int = 20
    max_x: int = (1 << 20) - 1
    variant_pgs: int = 1 << 21
    pool_pgs: int = 16384
    indep_pgs: int = 1 << 16        # one indep block wide enough to narrow
    sample: int = 4096
    block: int | None = None        # Mapper block (None: its default)
    ref_workers: int = 8            # CPU-pinned mapper_ref processes
    ec_iterations: int = 64
    sharded_pgs: int = 1 << 22
    sharded_stripes: int = 64


FULL = Sizes()
REHEARSAL = Sizes(object_size=256 << 10, in_flight=4, objects_83=4,
                  objects_42=2, degraded_reads=2, n_osds=256, hosts=16,
                  racks=4, max_x=4095, variant_pgs=1 << 12,
                  pool_pgs=256, indep_pgs=256, sample=64, block=1024,
                  ref_workers=0,
                  ec_iterations=2, sharded_pgs=1 << 13,
                  sharded_stripes=8)


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- per-phase accounting ---------------------------------------------------

_DEVMON_KEYS = ("jit_compiles", "jit_compile_seconds", "launches_pallas",
                "launches_xla", "launches_scalar", "launches_sharded",
                "path_mismatch", "h2d_bytes", "d2h_bytes",
                "quarantine_entries")


def _counters() -> dict:
    from ceph_tpu.crush.mapper import PERF as mapper_perf
    from ceph_tpu.utils.devmon import devmon
    d = devmon().perf.dump()
    out = {k: d.get(k, 0) for k in _DEVMON_KEYS}
    out["kernel_exec_failures"] = mapper_perf.dump().get(
        "kernel_exec_failures", 0)
    return out


def emit(phase: str, t0: float, before: dict, engine: str, **extra) -> dict:
    """Print the phase's line and fail on any device-health counter
    that moved: a quarantine, a path mismatch or a kernel failure means
    the answer came from a fallback."""
    after = _counters()
    d = {k: after[k] - before[k] for k in after}
    line = {
        "phase": phase,
        "wall_s": round(time.perf_counter() - t0, 3),
        "compile_s": round(float(d["jit_compile_seconds"]), 3),
        "compiles": int(d["jit_compiles"]),
        "launches": {k[len("launches_"):]: int(d[k]) for k in d
                     if k.startswith("launches_") and d[k]},
        "engine": engine,
        "h2d_bytes": int(d["h2d_bytes"]),
        "d2h_bytes": int(d["d2h_bytes"]),
    }
    line.update(extra)
    print(json.dumps(line), flush=True)
    for k in ("quarantine_entries", "path_mismatch",
              "kernel_exec_failures"):
        check(d[k] == 0, f"{phase}: {k} moved by {d[k]}")
    return line


# -- phase 1: the served EC path -------------------------------------------

async def _mon(c, cmd: dict):
    ret, rs, out = await c.client.mon_command(cmd)
    check(ret == 0, f"mon command {cmd.get('prefix')!r}: {rs}")
    return out


async def _acting(c, pool: str, oid: str) -> list[int]:
    out = await _mon(c, {"prefix": "osd map", "pool": pool, "object": oid})
    return json.loads(out)["acting"]


async def _bounded(coros, limit: int):
    sem = asyncio.Semaphore(limit)

    async def one(coro):
        async with sem:
            return await coro
    return await asyncio.gather(*[one(c) for c in coros])


_AGG_BAD = ("fallback_ops", "crc_fallbacks", "per_op_retries",
            "flush_failures", "quarantined_ops")


def _agg_totals(osds) -> dict:
    """Both aggregators' counters summed over all OSDs: launches and
    ops served, and everything that means the host served instead."""
    tot = dict.fromkeys(_AGG_BAD + ("encode_launches", "encode_stripes",
                                    "decode_ops"), 0)
    for o in osds:
        w, r = o.ec_agg.perf.dump(), o.ec_read_agg.perf.dump()
        for k in _AGG_BAD:
            tot[k] += w.get(k, 0) + r.get(k, 0)
        tot["encode_launches"] += w.get("batches", 0) + w.get("bypass", 0)
        tot["encode_stripes"] += w.get("stripes", 0)
        tot["decode_ops"] += r.get("ops", 0) + r.get("bypass", 0)
    return tot


async def _served(sz: Sizes, seed: int) -> dict:
    import numpy as np

    from ceph_tpu.cluster.vstart import Cluster
    from ceph_tpu.gf import pallas_kernels as pk
    from ceph_tpu.utils.devmon import devmon

    rng = np.random.default_rng(seed)
    pools = {"ec83": (EC83, sz.objects_83, 8),
             "ec42": (EC42, sz.objects_42, 4)}
    # ceph's own default down->out interval: the victim stays down+in,
    # so the degraded reads below are served by decode, not by backfill
    c = await Cluster(n_mons=1, n_osds=12, config={
        "mon_osd_down_out_interval": 600.0}).start()
    try:
        ios, payload, acting = {}, {}, {}
        for name, (profile, _n, _k) in pools.items():
            await _mon(c, {"prefix": "osd erasure-code-profile set",
                           "name": f"{name}-profile", "profile": profile})
            await _mon(c, {"prefix": "osd pool create", "pool": name,
                           "pg_num": 8, "pool_type": "erasure",
                           "erasure_code_profile": f"{name}-profile"})
        await c.wait_for_clean(timeout=300)
        t_write = time.perf_counter()
        for name, (_p, n_obj, _k) in pools.items():
            ios[name] = io = await c.client.open_ioctx(name)
            payload[name] = {
                f"{name}-obj{i}": rng.integers(
                    0, 256, sz.object_size, dtype=np.uint8).tobytes()
                for i in range(n_obj)}
            await _bounded([io.write_full(oid, data, timeout=600.0)
                            for oid, data in payload[name].items()],
                           sz.in_flight)
        t_write = time.perf_counter() - t_write
        t_read = time.perf_counter()
        for name, io in ios.items():
            got = await _bounded([io.read(oid, timeout=600.0)
                                  for oid in payload[name]], sz.in_flight)
            for oid, data in zip(payload[name], got):
                check(data == payload[name][oid],
                      f"served: {oid} read back differs")
            for oid in payload[name]:
                acting[oid] = await _acting(c, name, oid)
        t_read = time.perf_counter() - t_read
        # the victim holds a DATA shard (position < k) of as many
        # objects as possible: only a missing data shard forces decode
        def holds_data(osd: int, name: str, oid: str) -> bool:
            a = acting[oid]
            return osd in a and a.index(osd) < pools[name][2]
        victim = max(range(12), key=lambda o: (
            min(sz.degraded_reads, sum(holds_data(o, "ec83", oid)
                                       for oid in payload["ec83"])),
            sum(holds_data(o, "ec42", oid) for oid in payload["ec42"])))
        decode_before = _agg_totals(c.osds)["decode_ops"]
        await c.kill_osd(victim)
        await c.wait_for_osd_down(victim, timeout=120)
        t_deg = time.perf_counter()
        n_deg = {}
        for name, io in ios.items():
            oids = [oid for oid in payload[name]
                    if holds_data(victim, name, oid)][:sz.degraded_reads]
            n_deg[name] = len(oids)
            got = await _bounded([io.read(oid, timeout=600.0)
                                  for oid in oids], sz.in_flight)
            for oid, data in zip(oids, got):
                check(data == payload[name][oid],
                      f"served: degraded read of {oid} differs")
        t_deg = time.perf_counter() - t_deg
        check(n_deg["ec83"] == sz.degraded_reads,
              f"served: only {n_deg['ec83']} ec83 objects had a data "
              f"shard on osd.{victim}")
        check(n_deg["ec42"] >= 1,
              f"served: no ec42 object had a data shard on osd.{victim}")
        # which engine served, and that nothing fell to the host
        agg = _agg_totals(c.osds)
        engines = set()
        for o in c.osds:
            for pg in o.pgs.values():
                ec = getattr(pg, "ec", None)
                if ec is not None:
                    fused = ec.backend == "pallas" and pk.pallas_ok(
                        pg.sinfo.chunk_size, pg.k, pg.m)
                    engines.add(
                        f"k={pg.k} m={pg.m} chunk={pg.sinfo.chunk_size}"
                        f": backend={ec.backend} -> "
                        + ("fused pallas kernel" if fused
                           else "xla bitmatmul"))
        check(all(agg[k] == 0 for k in _AGG_BAD),
              f"served: host fallback counters moved: {agg}")
        check(agg["decode_ops"] > decode_before,
              "served: degraded reads never reached ECReadAggregator")
        dm = devmon().dump()
        check(dm["engine"] == EXPECT["platform"],
              f"served: devmon engine {dm['engine']!r}")
        return {
            "objects": {n: len(p) for n, p in payload.items()},
            "object_size": sz.object_size,
            "bytes_written": sum(len(d) for p in payload.values()
                                 for d in p.values()),
            "write_s": round(t_write, 3), "read_s": round(t_read, 3),
            "degraded_read_s": round(t_deg, 3),
            "degraded_reads": n_deg, "victim": victim,
            "encode_launches": agg["encode_launches"],
            "encode_stripes": agg["encode_stripes"],
            "decode_ops": agg["decode_ops"] - decode_before,
            "ec_engines": sorted(engines),
            "compiles_by_fn": {k: v["count"] for k, v in
                               dm.get("compiles_by_fn", {}).items()},
            "devmon_engine": dm["engine"],
        }
    finally:
        await c.stop()


def phase_served(sz: Sizes, seed: int) -> dict:
    t0, before = time.perf_counter(), _counters()
    info = asyncio.run(_served(sz, seed))
    return emit("served", t0, before, info.pop("devmon_engine"), **info)


# -- phase 2: placement -----------------------------------------------------

def _ref_init() -> None:
    # a worker is a second process: it must keep off the chip
    os.environ["JAX_PLATFORMS"] = "cpu"


def _ref_chunk(crush_map, ruleno: int, xs: list[int], numrep: int,
               ca_key):
    from ceph_tpu.crush import mapper_ref
    ca = crush_map.choose_args[ca_key] if ca_key is not None else None
    return [mapper_ref.do_rule(crush_map, ruleno, x, numrep,
                               choose_args=ca) for x in xs]


class Reference:
    """crush/mapper_ref.py over a sample, on CPU-pinned workers."""

    def __init__(self, workers: int):
        self.workers = workers
        self.pool = None

    def rows(self, crush_map, ruleno, xs, numrep, ca_key=None):
        xs = [int(x) for x in xs]
        if not self.workers:
            return _ref_chunk(crush_map, ruleno, xs, numrep, ca_key)
        if self.pool is None:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor
            self.pool = ProcessPoolExecutor(
                self.workers, initializer=_ref_init,
                mp_context=multiprocessing.get_context("spawn"))
        step = -(-len(xs) // (4 * self.workers))
        futs = [self.pool.submit(_ref_chunk, crush_map, ruleno,
                                 xs[i:i + step], numrep, ca_key)
                for i in range(0, len(xs), step)]
        return [row for f in futs for row in f.result()]

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)
            self.pool = None


def _compare(what: str, got, want_rows) -> None:
    import numpy as np
    from ceph_tpu.crush.types import ITEM_NONE
    got = np.asarray(got)
    for i, want in enumerate(want_rows):
        row = [int(o) for o in got[i] if o != ITEM_NONE]
        check(row == [int(o) for o in want],
              f"placement: {what} lane {i}: {row} != reference {want}")


def _check_path(what: str, mapper, ruleno: int, numrep: int) -> str:
    promised = mapper.mapping_path(ruleno, numrep)
    check(promised == EXPECT["crush_path"],
          f"placement: {what} plans {promised!r}, "
          f"want {EXPECT['crush_path']!r}")
    check(mapper.last_map_path == promised,
          f"placement: {what} promised {promised!r} but ran "
          f"{mapper.last_map_path!r}")
    return promised


def _indep_block(cmap, sz: Sizes, start: int) -> list:
    """``map_pgs`` of ``sz.indep_pgs`` ids from ``start`` by upstream's
    erasure rule (the ``rule_text`` of the benchmark's configuration
    ``crush-10k-ec83-indep``), 11 wide, against ``benchmark/reference``'s
    ``crush_indep_ref``, position by position, holes included. Returns
    the widths the block narrows to (none: it is under the floor)."""
    import pathlib

    import numpy as np

    from ceph_tpu.crush import builder, mapper as mapper_mod
    from ceph_tpu.crush.mapper import Mapper
    bench = pathlib.Path(__file__).resolve().parent / "benchmark"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from reference import crush_indep_ref, crush_ref
    rule_text = json.loads((bench / "configs" / "crush-10k-ec83-indep.json"
                            ).read_text())["rule_text"]
    root = cmap.rules[0].steps[0].arg1
    rid = builder.add_simple_rule(cmap, root, builder.TYPE_HOST, indep=True)
    rmap = crush_ref.build_map({"osds": sz.n_osds, "hosts": sz.hosts,
                                "racks": sz.racks, "failure_domain": "host"})
    steps = crush_indep_ref.parse_rule(rule_text, rmap)
    check([(s.op, s.arg1, s.arg2) for s in cmap.rules[rid].steps]
          == crush_indep_ref.step_codes(steps),
          "placement: indep: the program's erasure rule is not upstream's")
    mapper = Mapper(cmap, block=sz.block)
    xs = np.arange(start, start + sz.indep_pgs, dtype=np.uint32)
    got = np.asarray(mapper.map_pgs(rid, xs, 11)).astype(np.int64)
    check(mapper.last_map_path == "xla",
          f"placement: indep ran on {mapper.last_map_path!r}, not the rule VM")
    want = crush_indep_ref.map_batch(rmap, steps, xs, 11)
    differing = int((got != want).sum())
    check(got.shape == want.shape and differing == 0,
          f"placement: indep: {differing} of {want.size} positions differ "
          f"from crush_indep_ref")
    # ids up to the mapper's block run as one block of their own width
    return list(mapper_mod.narrow_widths(sz.indep_pgs))


def phase_placement(sz: Sizes, seed: int) -> dict:
    import numpy as np

    from ceph_tpu.bench import crush_sweep, crushtool
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.crush.tester import CrushTester
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import PGPool

    t0, before = time.perf_counter(), _counters()
    rng = np.random.default_rng(seed)
    ref = Reference(sz.ref_workers)
    paths = {}
    try:
        # (a) crushtool's own path, the README invocation at 10k OSDs
        argv = ["--build", "--num-osds", str(sz.n_osds),
                "--hosts", str(sz.hosts), "--racks", str(sz.racks),
                "--test", "--num-rep", "3", "--max-x", str(sz.max_x),
                "--show-statistics"]
        if sz.block:
            argv += ["--batch", str(sz.block)]
        res = crushtool.main(argv)
        check(res["total_x"] == sz.max_x + 1 and res["bad_mappings"] == 0,
              f"placement: crushtool {res}")
        args = crushtool.parse_args(argv)
        cmap = crushtool.build_map(args)
        tester = CrushTester(cmap, batch=args.batch)
        xs = rng.choice(sz.max_x + 1, size=min(sz.sample, sz.max_x + 1),
                        replace=False).astype(np.uint32)
        got = tester.mapper.map_pgs(args.rule, xs, 3)
        _compare("crushtool", got, ref.rows(cmap, args.rule, xs, 3))
        paths["crushtool"] = _check_path("crushtool", tester.mapper,
                                         args.rule, 3)
        crushtool_rate = res["mappings_per_second"]

        # (b) the production-shaped variants
        names = ("mixed_weight", "choose_args", "choose_args_quantized")
        variants = crush_sweep.sweep_rate_variants(
            sz.n_osds, sz.variant_pgs, 3, block=sz.block, variants=names)
        regress = crush_sweep.path_regressions(variants)
        check(not regress, f"placement: path_regressions {regress}")
        for name in names:
            check(variants[name]["path"] == EXPECT["crush_path"],
                  f"placement: {name} swept on {variants[name]['path']!r}")
            build, ca_key = crush_sweep.VARIANT_MAPS[name]
            vmap = build(sz.n_osds)
            mapper = Mapper(vmap, block=sz.block, choose_args=ca_key)
            xs = rng.choice(sz.variant_pgs, size=min(sz.sample,
                                                     sz.variant_pgs),
                            replace=False).astype(np.uint32)
            got = mapper.map_pgs(0, xs, 3)
            _compare(name, got, ref.rows(vmap, 0, xs, 3, ca_key))
            paths[name] = _check_path(name, mapper, 0, 3)

        # (c) one whole-pool pg_to_up_acting_osds
        omap = OSDMap(crush_sweep.canonical_map(sz.n_osds))
        pool = omap.add_pool(PGPool(id=1, pg_num=sz.pool_pgs, size=3,
                                    crush_rule=0))
        seeds = np.arange(sz.pool_pgs, dtype=np.uint32)
        t_pool = time.perf_counter()
        up, up_primary, acting, _actp = omap.pg_to_up_acting_osds(1, seeds)
        t_pool = time.perf_counter() - t_pool
        check(up.shape == (sz.pool_pgs, 3) and (up == acting).all(),
              "placement: whole-pool up/acting shape")
        pick = rng.choice(sz.pool_pgs, size=min(sz.sample, sz.pool_pgs),
                          replace=False)
        pps = pool.raw_pg_to_pps(seeds[pick], xp=np)
        want = ref.rows(omap.crush, 0, pps, 3)
        _compare("pg_to_up_acting_osds", up[pick], want)
        check([int(p) for p in up_primary[pick]] == [w[0] for w in want],
              "placement: up_primary differs from the reference")
        paths["pool"] = _check_path("pg_to_up_acting_osds",
                                    omap.serving_mapper(1), 0, 3)

        # (d) an EC pool's rule, 11 wide, on the rule VM: one block of
        # consecutive ids, at full size wide enough to finish its later
        # rounds narrow, every position against the benchmark's plain
        # crush_choose_indep (the sweeps above are 3 wide)
        t_indep = time.perf_counter()
        indep_width = _indep_block(cmap, sz, int(rng.integers(1, 1 << 20)))
        t_indep = time.perf_counter() - t_indep
    finally:
        ref.close()
    return emit(
        "placement", t0, before, "/".join(sorted(set(paths.values()))),
        paths=paths, sample=int(min(sz.sample, sz.pool_pgs)),
        crushtool_mappings_per_s_cold=round(crushtool_rate, 1),
        variants={n: {k: v[k] for k in ("mappings_per_s", "n_pgs", "path",
                                        "kernel_lanes", "candidate_fold")
                      if k in v} for n, v in variants.items()},
        pool_pgs=sz.pool_pgs, pool_map_s=round(t_pool, 3),
        indep_pgs=sz.indep_pgs, indep_narrow_widths=indep_width,
        indep_s=round(t_indep, 3))


# -- phase 3: the kernels ---------------------------------------------------

def _bench(workload: str, k: int, m: int, sz: Sizes):
    from ceph_tpu.bench import ec_benchmark
    argv = ["--plugin", "jax", "--workload", workload,
            "--size", str(sz.object_size),
            "--iterations", str(sz.ec_iterations),
            "--parameter", f"k={k}", "--parameter", f"m={m}",
            "--parameter", "technique=reed_sol_van"]
    if workload == "decode":
        argv += ["--erasures", "2"]
    return ec_benchmark.ErasureCodeBench(ec_benchmark.parse_args(argv))


def phase_kernels(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.gf import pallas_kernels as pk
    from ceph_tpu.utils import roofline

    t0, before = time.perf_counter(), _counters()
    kind = jax.devices()[0].device_kind
    if EXPECT["platform"] == "tpu":
        check(roofline.device_spec() is not None,
              f"kernels: no roofline peaks for device_kind {kind!r}")
    rng = np.random.default_rng(seed)
    rows = []
    for k, m in ((8, 3), (4, 2)):
        for workload in ("encode", "decode"):
            b = _bench(workload, k, m, sz)
            ec = b.ec
            check(ec.backend == EXPECT["ec_backend"],
                  f"kernels: {k}+{m} resolved backend {ec.backend!r}")
            data = jnp.asarray(b._make_data(rng))
            i = int(rng.integers(data.shape[0]))
            if workload == "encode":
                fn, arg = ec.encode_batch, data
                want = ec.encode_batch_reference(np.asarray(data[i:i + 1]))
            else:
                parity = ec.encode_batch(data)
                full = jnp.concatenate([data, parity], axis=1)
                erased = list(range(2))
                avail = [c for c in range(k + m) if c not in erased][:k]
                arg = full[:, jnp.asarray(avail), :]
                fn = lambda c: ec.decode_batch(erased, avail, c)  # noqa
                want = np.asarray(data[i:i + 1, :2])
            # eager first: it builds the per-pattern decode plan from
            # concrete values (under a trace they would be tracers)
            out = fn(arg)
            if ec.backend == "pallas":
                # the compiled Mosaic kernel, not the interpreter and
                # not the XLA bitmatmul the plugin falls back to
                check(pk.pallas_ok(int(arg.shape[-1]), k,
                                   m if workload == "encode" else 2),
                      f"kernels: {k}+{m} chunk {arg.shape[-1]} is not "
                      f"routed to the fused kernel")
                hlo = jax.jit(fn).lower(arg).as_text()
                check("tpu_custom_call" in hlo,
                      f"kernels: {k}+{m} {workload} has no Mosaic call")
            check(np.array_equal(np.asarray(out[i:i + 1]), want),
                  f"kernels: {k}+{m} {workload} stripe {i} differs from "
                  f"the reference")
            # one warm launch: wall to block_until_ready, beside the
            # readback-anchored slope utils/timing.py measures
            t1 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            bur = time.perf_counter() - t1
            r = b.run()
            check(r["backend"] == EXPECT["ec_backend"],
                  f"kernels: bench ran on {r['backend']!r}")
            rows.append({
                "workload": workload, "k": k, "m": m,
                "chunk": r["chunk_size"], "batch": r["batch"],
                "GiB/s": round(r["GiB/s"], 2), "device_kind": kind,
                "roofline_GiB/s": r["roofline_GiB/s"] and
                round(r["roofline_GiB/s"], 1),
                "block_until_ready_s": round(bur, 6),
                "readback_slope_s": round(r["seconds"], 6),
                "bur_over_slope": round(bur / r["seconds"], 3),
                "slope_overhead_s": r["timing"].get("overhead_s")})
    return emit("kernels", t0, before, EXPECT["ec_backend"], rows=rows)


# -- --chips 4: the sharded paths ------------------------------------------

def phase_sharded(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ceph_tpu.bench.crush_sweep import canonical_map
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.crush.sharded_sweep import sharded_sweep
    from ceph_tpu.ec import factory
    from ceph_tpu.parallel import make_mesh, sharded_encode

    t0, before = time.perf_counter(), _counters()
    mesh = make_mesh(jax.devices()[:4])

    def spread(x) -> int:
        return len({s.device for s in x.addressable_shards})

    mapper = Mapper(canonical_map(sz.n_osds), block=sz.block)
    t1 = time.perf_counter()
    c1, bad1 = mapper.sweep(0, 0, sz.sharded_pgs, 3)
    c1 = np.asarray(c1)
    single_path = mapper.last_map_path
    t_single = time.perf_counter() - t1
    t1 = time.perf_counter()
    c4, bad4 = sharded_sweep(mesh, mapper, 0, 0, sz.sharded_pgs, 3)
    sweep_spread = spread(c4)
    c4 = np.asarray(c4)
    t_sharded = time.perf_counter() - t1
    check(np.array_equal(c1, c4) and int(bad1) == int(bad4),
          "sharded: sweep counts differ from the single-device sweep")
    check(int(c4.sum()) == 3 * sz.sharded_pgs and int(bad4) == 0,
          f"sharded: {int(c4.sum())} placements, {int(bad4)} bad")
    want_path = EXPECT["crush_path"] + "+sharded"
    check(mapper.last_map_path == want_path,
          f"sharded: path {mapper.last_map_path!r}, want {want_path!r}")
    check(sweep_spread == 4, f"sharded: sweep on {sweep_spread} devices")
    # the walls above hold the compiles; once more, warm
    t1 = time.perf_counter()
    np.asarray(mapper.sweep(0, 0, sz.sharded_pgs, 3)[0])
    t_single_warm = time.perf_counter() - t1
    t1 = time.perf_counter()
    np.asarray(sharded_sweep(mesh, mapper, 0, 0, sz.sharded_pgs, 3)[0])
    t_sharded_warm = time.perf_counter() - t1

    ec = factory(" ".join(EC83))
    kern = ec._encode_kernel
    C = sz.object_size // 8
    data = np.random.default_rng(seed).integers(
        0, 256, (sz.sharded_stripes, 8, C), dtype=np.uint8)
    single = np.asarray(ec.encode_batch(jnp.asarray(data)))
    placed = jax.device_put(data, NamedSharding(mesh, P("shard")))
    out = sharded_encode(mesh, kern.bitmatrix, kern.lo, kern.hi, placed)
    enc_spread = spread(out)
    check(np.array_equal(np.asarray(out), single),
          "sharded: sharded_encode differs from the single-device encode")
    check(enc_spread == 4, f"sharded: encode on {enc_spread} devices")
    return emit("sharded", t0, before, want_path,
                sweep_pgs=sz.sharded_pgs, single_sweep_path=single_path,
                single_sweep_s=round(t_single, 3),
                sharded_sweep_s=round(t_sharded, 3),
                single_sweep_warm_s=round(t_single_warm, 4),
                sharded_sweep_warm_s=round(t_sharded_warm, 4),
                encode_stripes=sz.sharded_stripes, encode_chunk=C,
                sweep_devices=sweep_spread, encode_devices=enc_spread)


# -- driver -----------------------------------------------------------------

def run(chips: int, sz: Sizes, seed: int, only: str | None = None) -> dict:
    """All phases for this chip count (or just ``only``); raises on the
    first failure."""
    from ceph_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = device_info()
    check(dev["platform"] == EXPECT["platform"],
          f"no accelerator: jax.devices()[0].platform is "
          f"{dev['platform']!r}")
    check(dev["count"] == chips,
          f"{dev['count']} devices visible, --chips {chips} asked")
    phases = (phase_sharded,) if chips == 4 else \
        (phase_served, phase_placement, phase_kernels)
    for phase in phases:
        if only in (None, phase.__name__[len("phase_"):]):
            phase(sz, seed)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny counts, same shapes (still needs the TPU)")
    ap.add_argument("--only", choices=("served", "placement", "kernels"),
                    help="one phase of the one-chip run (fault finding)")
    args = ap.parse_args(argv)
    try:
        dev = run(args.chips, REHEARSAL if args.rehearsal else FULL,
                  args.seed, args.only)
    except Exception as e:              # the last line is the verdict
        import traceback
        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

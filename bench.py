"""Driver benchmark: prints ONE JSON line with the headline metric.

Headline (BASELINE.md north star): EC encode throughput at k=8, m=3 on
4 MiB objects — the ``ceph_erasure_code_benchmark plugin=isa k=8 m=3``
configuration. ``vs_baseline`` compares against 7.5 GiB/s, the midpoint of
the ISA-L single-core estimate recorded in BASELINE.md (the reference
publishes no numbers in-repo).

Methodology note (round 2): round 1's number (9,317 GiB/s) was measured
with a dispatch-timed loop and is RETRACTED. All rates here come from
the chained readback-anchored slope method (ceph_tpu/utils/timing.py) and
pass the physical roofline guard (ceph_tpu/utils/roofline.py); the
methodology fields are included in the output so the number can be audited.

Secondary metrics in ``detail``: decode throughput, MFU, and the CRUSH
north-star ``crush_mappings_per_s`` (batched pg->osd mapping rate).
"""

import json
import os
import re
import sys
import time
import traceback

from ceph_tpu.bench import device_stamp
from ceph_tpu.utils.compile_cache import enable_compile_cache

BASELINE_GIBS = 7.5  # ISA-L RS k=8,m=3 single-core (BASELINE.md external row)

_ANSI = re.compile(r"\x1b\[[0-9;]*m")


def _short_err(limit: int = 400) -> str:
    """Compact one-line rendering of the current exception.

    Round 4's lesson: a raw ``format_exc`` of a TPU compile error embeds
    kilobytes of runtime log (with ANSI escapes) into the JSON line and
    the driver fails to parse it — the whole round's number is lost.
    Strip escapes, keep the last few non-empty lines, hard-cap length."""
    s = _ANSI.sub("", traceback.format_exc(limit=2))
    lines = [ln.strip() for ln in s.splitlines() if ln.strip()]
    return " | ".join(lines[-4:])[:limit]


def ec_metrics() -> tuple[dict, dict, dict]:
    from ceph_tpu.bench.ec_benchmark import ErasureCodeBench, parse_args

    # "auto" resolves to the fused pallas kernel on TPU — tested
    # byte-exact vs the XLA path (tests/test_gf.py TestPallasKernel) and
    # measured ~1.7x bitmatmul on v5e (~103 vs ~60 GiB/s) after the
    # round-4 rewrite (mod-2 absorb + block-diag contraction) — and to
    # bitmatmul elsewhere (pallas would only interpret on CPU).
    backend = os.environ.get("CEPH_TPU_BENCH_BACKEND", "auto")
    common = [
        "--plugin", "jax", "--size", str(4 << 20),
        "--parameter", "k=8", "--parameter", "m=3",
        "--parameter", f"backend={backend}",
        "--parameter", "technique=reed_sol_van",
    ]
    enc = ErasureCodeBench(parse_args(
        common + ["--iterations", "1024", "--workload", "encode",
                  "--slope-steps", "16", "96"])).run()
    dec = ErasureCodeBench(parse_args(
        common + ["--iterations", "1024", "--workload", "decode",
                  "--erasures", "2", "--slope-steps", "16", "96"])).run()
    # Streamed row (SURVEY §7: report resident AND streamed): H2D inside
    # the loop, small steps — the host-transfer-bound rate. Not
    # re-measured on a local chip.
    stream = ErasureCodeBench(parse_args(
        common + ["--iterations", "8", "--batch", "8",
                  "--workload", "encode", "--stream"])).run()
    return enc, dec, stream


def ec_streaming_metric(resident_gibs: float | None) -> dict:
    """Round-13 EC data path at production traffic: the cross-op
    encode aggregator (concurrent ops coalescing into padded batched
    launches vs the per-op `osd_ec_agg=off` baseline) and the
    double-buffered H2D/D2H streaming pipeline, against the resident
    kernel rate. The claim the section pins: aggregated multi-op
    encode throughput within 2x of the resident number on TPU
    (`ec_agg_within_2x` in the compact tail; CPU boxes run a smoke
    size with the same schema)."""
    from ceph_tpu.bench.ec_streaming import ec_streaming_section

    return ec_streaming_section(resident_gibs=resident_gibs)


def ec_daemon_path_metric() -> dict:
    """Round-19 read-side data path: concurrent degraded-read decodes
    through ``osd/ec_aggregator.ECReadAggregator`` (coalesced padded batched
    decode launches vs the per-op ``osd_ec_read_agg=off`` baseline),
    against the resident decode kernel rate. The claim the section
    pins: the aggregated daemon-path rate lands within 2x of the
    resident number on TPU (``daemon_within_2x_resident`` in the
    compact tail; CPU boxes run a smoke size with the same schema and
    an explicit asyncio-bound caveat)."""
    from ceph_tpu.bench.ec_daemon_path import ec_daemon_path_section

    return ec_daemon_path_section()


def crush_metric() -> dict:
    """North-star #2: batched CRUSH mappings/s on a 10k-OSD straw2 map.

    Headline = uniform map (the fused Pallas kernel path on TPU);
    ``variants`` adds the production-shaped mixed-weight and
    choose_args rates so the slow paths are measured every round
    (VERDICT r3 Weak #3)."""
    from ceph_tpu.bench.crush_sweep import sweep_rate, sweep_rate_variants

    n_pgs = int(os.environ.get("CEPH_TPU_BENCH_CRUSH_PGS", str(1 << 21)))
    res = sweep_rate(n_osds=10240, n_pgs=n_pgs, num_rep=3)
    # LOUD (round 10): a row whose built kernel plan silently degraded
    # to xla/scalar mid-run is a recorded regression, not a mystery
    # slowdown — the PR 4 choose_args cliff hid here. The headline
    # row's verdict must survive even when the variants pass crashes.
    regs = []
    if "path_expected_vs_actual" in res:
        regs.append(f"uniform: {res['path_expected_vs_actual']}")
    try:
        res["variants"] = sweep_rate_variants(
            n_osds=10240, n_pgs=n_pgs, num_rep=3,
            variants=("mixed_weight", "choose_args",
                      "choose_args_quantized"))
        from ceph_tpu.bench.crush_sweep import path_regressions
        regs += path_regressions(res["variants"])
    except Exception:
        res["variants_error"] = _short_err()
    if regs:
        res["path_regressions"] = regs
    return res


def crush_multichip_metric(single_rate: float | None) -> dict:
    """Round-10 pod-scale row: a MEASURED full sweep on a mesh over
    every available device (the v5e-8's 8 chips under the driver; a
    single chip degenerates to a 1-device mesh) — the number the
    paper's ≈5 s pod figure only ever estimated via linear scaling.
    ``seconds_100M`` is the measured wall itself at the default
    100M-PG target (``extrapolated: false``); per-device scaling
    efficiency is reported against the single-chip row."""
    import jax

    from ceph_tpu.bench.crush_sweep import canonical_map, sweep_rate
    from ceph_tpu.bench.multichip import measured_sweep
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.parallel import make_mesh

    devices = jax.devices()
    # the full 100M target is a TPU-rate number; a CPU dev box running
    # bench.py would spend hours on it through the rule VM — default
    # to a smoke size there (env override always wins)
    default_pgs = 100_000_000 \
        if devices[0].platform == "tpu" else 1 << 20
    n_pgs = int(os.environ.get("CEPH_TPU_BENCH_MULTICHIP_PGS",
                               str(default_pgs)))
    mesh = make_mesh(devices)
    mapper = Mapper(canonical_map(10240))
    res = measured_sweep(mesh, mapper, n_pgs, 3)
    if single_rate is None:
        single_rate = sweep_rate(n_osds=10240, n_pgs=1 << 21,
                                 num_rep=3)["mappings_per_s"]
    res["single_device_mappings_per_s"] = single_rate
    res["scaling_efficiency"] = round(
        res["mappings_per_s"] / (single_rate * len(devices)), 3)
    return res


def balancer_metric() -> dict:
    """Balancer convergence at scale (VERDICT r3 ask #10): wall time of
    calc_pg_upmaps on a canonical-scale map, plus the Mapper lifecycle
    counter DELTAS for the run — pack/compile traffic at 10k OSDs is a
    recorded number now, not a guess."""
    from ceph_tpu.bench import osdmaptool
    from ceph_tpu.crush.mapper import PERF

    n_osds = int(os.environ.get("CEPH_TPU_BENCH_BAL_OSDS", "10240"))
    pgs = int(os.environ.get("CEPH_TPU_BENCH_BAL_PGS", "16384"))
    iters = int(os.environ.get("CEPH_TPU_BENCH_BAL_ITERS", "40"))
    t0 = time.perf_counter()
    m = osdmaptool.create_simple(n_osds, pgs, 3, erasure=False)
    build_s = time.perf_counter() - t0
    before = PERF.dump()
    t0 = time.perf_counter()
    changes = m.calc_pg_upmaps(max_deviation=5, max_iterations=iters)
    bal_s = time.perf_counter() - t0
    after = PERF.dump()
    counters = {k: round(after[k] - before[k], 4)
                for k in after if isinstance(after[k], (int, float))}
    return {"n_osds": n_osds, "pg_num": pgs, "max_iterations": iters,
            "upmap_changes": changes,
            "build_seconds": round(build_s, 3),
            "balance_seconds": round(bal_s, 3),
            "seconds_per_iteration": round(bal_s / max(iters, 1), 4),
            "mapper_counter_deltas": counters}


def mapping_engine_metric() -> dict:
    """Round-6 serving layers: the delta-remap path of OSDMapMapping
    (one-OSD incremental: remapped PGs + wall time vs a from-scratch
    resweep) and the epoch-keyed scalar cache hit rate — the numbers
    behind 'steady-state ops never re-enter the mapper'."""
    from ceph_tpu.bench import osdmaptool
    from ceph_tpu.osd.osdmap import Incremental
    from ceph_tpu.osd.osdmap_mapping import OSDMapMapping

    n_osds = int(os.environ.get("CEPH_TPU_BENCH_MAP_OSDS", "1024"))
    pgs = int(os.environ.get("CEPH_TPU_BENCH_MAP_PGS", "8192"))
    m = osdmaptool.create_simple(n_osds, pgs, 3, erasure=False)
    t0 = time.perf_counter()
    mm = OSDMapMapping(m)
    initial_s = time.perf_counter() - t0
    m.apply_incremental(Incremental(epoch=m.epoch + 1, new_down=[7]))
    t0 = time.perf_counter()
    mm.update(m)
    delta_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    OSDMapMapping(m)
    scratch_s = time.perf_counter() - t0
    # scalar memo (no table attached yet): one miss fills the
    # per-epoch memo, repeated op-targeting lookups hit it
    m.mapping_cache_hits = m.mapping_cache_misses = 0
    for _ in range(256):
        m.pg_to_acting_primary(1, 5)
    memo_hits, memo_misses = (m.mapping_cache_hits,
                              m.mapping_cache_misses)
    # attached table: serves every lookup at its epoch outright
    m.attach_mapping(mm)
    m.mapping_cache_hits = m.mapping_cache_misses = 0
    for _ in range(256):
        m.pg_to_acting_primary(1, 5)
    return {"n_osds": n_osds, "pg_num": pgs,
            "initial_sweep_seconds": round(initial_s, 4),
            "delta_update_seconds": round(delta_s, 4),
            "delta_remap_pgs": mm.last_remap_pgs,
            "full_resweep_seconds": round(scratch_s, 4),
            "delta_speedup": round(scratch_s / max(delta_s, 1e-9), 1),
            "memo_hits": memo_hits,
            "memo_misses": memo_misses,
            "cache_hits": m.mapping_cache_hits,
            "cache_misses": m.mapping_cache_misses}


def mds_metric() -> dict:
    """Round-7 metadata plane: aggregate + per-rank metadata ops/s at
    N = 1/2/4 active MDS ranks. FIXED client parallelism (4 writers,
    each its own client + subtree) distributed round-robin across the
    ranks, so the rows isolate rank scaling rather than client
    scaling — per rank, mutations serialize on that rank's journal
    object (per-object PG pipeline), which is exactly the contention
    multi-active relieves. The number that must move: aggregate ops/s
    increasing 1 -> 2 actives (rank-scaling regressions show here)."""
    import asyncio

    async def one(n_active: int, writers: int = 4,
                  ops_per_writer: int = 24) -> dict:
        from ceph_tpu.cephfs.client import CephFSClient
        from ceph_tpu.cluster.vstart import Cluster
        c = await Cluster(n_mons=1, n_osds=3,
                          config={"mds_bal_interval": 0.0}).start()
        try:
            await c.start_fs(n_mds=n_active, max_mds=n_active,
                             timeout=120)
            monmap = c.client.monc.monmap
            cl0 = await CephFSClient.create(monmap, None, "cephfs",
                                            keyring=c.keyring)
            for w in range(writers):
                await cl0.mkdir(f"/d{w}")
                if w % n_active:
                    await c.subtree_pin(f"/d{w}", w % n_active)
            clients = [cl0] + [
                await CephFSClient.create(monmap, None, "cephfs",
                                          keyring=c.keyring)
                for _ in range(1, writers)]

            async def load(w: int, cl) -> float:
                t0 = time.perf_counter()
                for i in range(ops_per_writer):
                    await cl.write_file(f"/d{w}/bench-{i}",
                                        b"x" * 64)
                return ops_per_writer / (time.perf_counter() - t0)
            t0 = time.perf_counter()
            rates = await asyncio.gather(
                *[load(w, cl) for w, cl in enumerate(clients)])
            wall = time.perf_counter() - t0
            per_rank: dict[str, float] = {}
            for w, rate in enumerate(rates):
                r = str(w % n_active)
                per_rank[r] = round(per_rank.get(r, 0.0) + rate, 1)
            for cl in clients:
                await cl.unmount()
            return {
                "ops": writers * ops_per_writer,
                "writers": writers,
                "aggregate_ops_per_s": round(
                    writers * ops_per_writer / wall, 1),
                "per_rank_ops_per_s": per_rank,
            }
        finally:
            await c.stop()

    return {f"max_mds_{n}": asyncio.run(one(n)) for n in (1, 2, 4)}


def tracing_metric() -> dict:
    """Round-9 observability layer: ops/s on the replicated cluster
    write path at trace_sampling_rate 0.0 vs 1.0, plus a tracing-off
    baseline (trace_slow_keep_s=0 disables even the tail-retention
    timing). The number that must hold: the DISABLED path
    (sampling 0, tail tracking on — the production default) stays
    within noise (<5%) of the off baseline; full sampling's cost is
    reported so the layer's price is pinned in the BENCH trajectory."""
    import asyncio

    async def one(rate: float, slow_keep: float,
                  n_ops: int = 160) -> float:
        from ceph_tpu.cluster.vstart import Cluster
        c = await Cluster(n_mons=1, n_osds=3, config={
            "trace_sampling_rate": rate,
            "trace_slow_keep_s": slow_keep}).start()
        try:
            await c.client.pool_create("bench", pg_num=8)
            await c.wait_for_clean(timeout=120)
            io = await c.client.open_ioctx("bench")
            for i in range(24):                      # warm the path
                await io.write_full(f"warm-{i}", b"x" * 1024)
            t0 = time.perf_counter()
            for i in range(n_ops):
                await io.write_full(f"obj-{i % 16}", b"x" * 1024)
            return n_ops / (time.perf_counter() - t0)
        finally:
            await c.stop()

    off = asyncio.run(one(0.0, 0.0))          # layer fully off
    disabled = asyncio.run(one(0.0, 30.0))    # default: tail-only
    full = asyncio.run(one(1.0, 30.0))        # every op traced
    disabled_overhead = (off - disabled) / off * 100.0
    full_overhead = (off - full) / off * 100.0
    return {
        "write_ops_per_s_tracing_off": round(off, 1),
        "write_ops_per_s_sampling_0": round(disabled, 1),
        "write_ops_per_s_sampling_1": round(full, 1),
        "disabled_overhead_pct": round(disabled_overhead, 2),
        "full_sampling_overhead_pct": round(full_overhead, 2),
        # the assertion the satellite pins: disabled-path cost is
        # noise (single-run cluster benches jitter a few percent, so
        # the flag — not a hard error — records the verdict)
        "disabled_within_noise": bool(disabled_overhead < 5.0),
    }


def telemetry_metric() -> dict:
    """Round-12 telemetry plane: cluster write-path ops/s with the
    daemon->mgr report loop OFF (mgr_stats_period=0), at the default
    period, and at 10x the period. The number that must hold: the
    default report loop stays within noise (<5%) of the off baseline
    (``telemetry_within_noise`` in the compact tail line) — same
    verdict shape as the round-9 tracing section. Unlike that
    section, all three legs run inside ONE cluster by flipping the
    LIVE ``mgr_stats_period`` knob (the shared-cfg dict pattern):
    separate cluster spins in one process jitter >10% run-to-run,
    which would swamp the report loop's actual cost — in-cluster
    A/B/A alternation with a median collapses that to per-burst
    noise."""
    import asyncio
    import statistics

    async def measure() -> dict[float, float]:
        from ceph_tpu.cluster.vstart import Cluster
        from ceph_tpu.mgr.modules import PrometheusModule
        c = await Cluster(n_mons=1, n_osds=3,
                          config={"mgr_stats_period": 0.0},
                          mgr_modules=[PrometheusModule]).start()
        try:
            await c.client.pool_create("bench", pg_num=8)
            await c.wait_for_clean(timeout=120)
            io = await c.client.open_ioctx("bench")
            for i in range(24):                      # warm the path
                await io.write_full(f"warm-{i}", b"x" * 1024)
            samples: dict[float, list[float]] = {
                0.0: [], 0.25: [], 2.5: []}
            order = list(samples)
            for rep in range(5):
                # rotate the leg order per rep: within-cluster drift
                # (PG logs filling toward their trim cap, allocator
                # state) is monotone in time, and a constant order
                # would charge it to whichever leg always runs last
                rot = rep % len(order)
                for period in order[rot:] + order[:rot]:
                    c.cfg["mgr_stats_period"] = period
                    await asyncio.sleep(0.6)  # loops read it LIVE
                    t0 = time.perf_counter()
                    for i in range(160):
                        await io.write_full(f"obj-{i % 16}",
                                            b"x" * 1024)
                    samples[period].append(
                        160 / (time.perf_counter() - t0))
            return samples
        finally:
            await c.stop()

    samples = asyncio.run(measure())
    legs = {p: statistics.median(v) for p, v in samples.items()}
    off = legs[0.0]                      # report loop disabled
    default = legs[0.25]                 # the vstart default period
    slow10 = legs[2.5]                   # 10x period
    overhead = (off - default) / off * 100.0
    # the off leg's own within-run spread IS the measurement's noise
    # floor (shared boxes schedule-jitter way past 5%): the verdict
    # asks whether the default report loop's cost is distinguishable
    # from that floor, and both raw numbers stay in the record
    spread = (max(samples[0.0]) - min(samples[0.0])) / off * 100.0
    return {
        "write_ops_per_s_reporting_off": round(off, 1),
        "write_ops_per_s_default_period": round(default, 1),
        "write_ops_per_s_10x_period": round(slow10, 1),
        "report_overhead_pct": round(overhead, 2),
        "noise_floor_pct": round(spread, 2),
        # the flag — not a hard error — records the verdict
        "telemetry_within_noise": bool(
            overhead < max(5.0, spread)),
    }


def qos_metric() -> dict:
    """Round-11 op-QoS layer: a 2-tenant hot/cold mix — ops/s + p99
    for the COLD tenant at its solo baseline, under FIFO admission,
    and under the dmClock scheduler. The claim the section pins: the
    scheduler holds the cold tenant's p99 within 2x of its solo run
    while FIFO (hot tenant at ~10x offered load) does not
    (``scheduler_protects_cold``)."""
    import asyncio

    async def run() -> dict:
        from ceph_tpu.cluster.vstart import Cluster
        from ceph_tpu.msg import Keyring as _Keyring
        from ceph_tpu.rados import Rados as _Rados
        from ceph_tpu.sim.thrasher import Thrasher
        c = await Cluster(n_mons=1, n_osds=3, config={
            # a small dispatch cap makes admission ordering the
            # bottleneck (the thing being measured), not store speed
            "osd_client_message_cap": 4,
            "osd_op_queue": "mclock"}).start()
        try:
            await c.client.pool_create("qos", pg_num=8)
            await c.wait_for_clean(timeout=120)
            ret, rs, out = await c.client.mon_command(
                {"prefix": "auth get-or-create",
                 "entity": "client.cold"})
            assert ret == 0, rs
            key = bytes.fromhex(json.loads(out)["key"])
            cold = _Rados(c.monmap, name="client.cold",
                          keyring=_Keyring({"client.cold": key}),
                          config=c.cfg)
            await cold.connect()
            io_cold = await cold.open_ioctx("qos")
            io_hot = await c.client.open_ioctx("qos")
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "osd client-profile", "op": "set",
                 "entity": "client.cold", "reservation": 20.0,
                 "weight": 4.0, "limit": 0.0})
            assert ret == 0, rs
            # settle + warm: the profile commit bumps the map epoch
            # and first ops pay connection setup — keep both out of
            # the solo baseline
            await c.wait_for_clean(timeout=60)
            for i in range(6):
                await io_cold.write_full(f"warm-c-{i}", b"w" * 256)
                await io_hot.write_full(f"warm-h-{i}", b"w" * 256)
            th = Thrasher(c, seed=7)
            solo = await th.qos_storm(io_cold, io_hot, writes=24,
                                      hot_parallel=0)
            c.cfg["osd_op_queue"] = "fifo"
            fifo = await th.qos_storm(io_cold, io_hot, writes=24,
                                      hot_parallel=4, hot_burst=16)
            c.cfg["osd_op_queue"] = "mclock"
            mclock = await th.qos_storm(io_cold, io_hot, writes=24,
                                        hot_parallel=4, hot_burst=16)
            await cold.shutdown()
            # the verdict compares p95 (structural queueing delay) —
            # at this sample count p99 is the max, owned by one
            # GC/event-loop blip; p99s stay in the record
            floor = max(2.0 * solo["cold_p99_s"], 0.05)
            return {
                "cold_solo": solo, "cold_under_fifo": fifo,
                "cold_under_mclock": mclock,
                "fifo_p99_ratio": round(
                    fifo["cold_p99_s"] /
                    max(solo["cold_p99_s"], 1e-9), 2),
                "mclock_p99_ratio": round(
                    mclock["cold_p99_s"] /
                    max(solo["cold_p99_s"], 1e-9), 2),
                "scheduler_protects_cold": bool(
                    mclock["cold_p95_s"] <= floor <
                    fifo["cold_p95_s"]),
            }
        finally:
            await c.stop()

    return asyncio.run(run())


def tuning_metric() -> dict:
    """Round-17 self-driving tuner: the hot-pool-burst storm with the
    mgr TunerModule ``off`` (static config) vs ``drive`` (closing the
    loop), both legs inside ONE cluster with the leg order rotated
    per rep and medians across reps (the round-12 in-cluster A/B
    discipline — separate cluster spins jitter >10%). The claim the
    section pins: in drive mode the tuner's hot-pool protector
    commits a tightened client-profile on the aggressor and the cold
    tenant's p95 stays at-or-under the static run's, without
    collapsing aggregate throughput (``tuner_protects_cold``)."""
    import asyncio
    import statistics

    async def run() -> dict:
        from ceph_tpu.cluster.vstart import Cluster
        from ceph_tpu.mgr.tuner import TunerModule
        from ceph_tpu.msg import Keyring as _Keyring
        from ceph_tpu.rados import Rados as _Rados
        from ceph_tpu.sim.thrasher import Thrasher
        c = await Cluster(n_mons=1, n_osds=3,
                          mgr_modules=[TunerModule], config={
            "osd_client_message_cap": 4,
            "osd_op_queue": "mclock",
            "mgr_tuner_mode": "off",
            # smoke-speed control loop: fast ticks, short hysteresis,
            # trip threshold sized to the storm's offered load, pg
            # stats refreshed faster than the tick so consecutive
            # breach windows see fresh rates
            "osd_stats_interval": 0.1,
            "mgr_tuner_interval": 0.2,
            "mgr_tuner_act_ticks": 2,
            "mgr_tuner_revert_ticks": 4,
            "mgr_tuner_hot_pool_min_ops": 5.0,
            # keep the recovery governor quiet (no backfill here):
            # the section isolates the hot-pool protector
            "mgr_tuner_qos_floor_ms": 5000.0}).start()
        try:
            await c.client.pool_create("cold", pg_num=8)
            await c.client.pool_create("hot", pg_num=8)
            await c.wait_for_clean(timeout=120)

            async def tenant(entity: str) -> _Rados:
                ret, rs, out = await c.client.mon_command(
                    {"prefix": "auth get-or-create",
                     "entity": entity})
                assert ret == 0, rs
                key = bytes.fromhex(json.loads(out)["key"])
                r = _Rados(c.monmap, name=entity,
                           keyring=_Keyring({entity: key}),
                           config=c.cfg)
                await r.connect()
                return r
            cold = await tenant("client.cold")
            hot = await tenant("client.hot")
            io_cold = await cold.open_ioctx("cold")
            io_hot = await hot.open_ioctx("hot")
            await c.wait_for_clean(timeout=60)
            for i in range(6):
                await io_cold.write_full(f"warm-c-{i}", b"w" * 256)
                await io_hot.write_full(f"warm-h-{i}", b"w" * 256)
            th = Thrasher(c, seed=17)
            samples: dict[str, list[dict]] = {"off": [], "drive": []}
            committed = reverted = 0
            order = ["off", "drive"]
            for rep in range(2):
                rot = rep % len(order)
                for leg in order[rot:] + order[:rot]:
                    ret, _, out = await c.client.mon_command(
                        {"prefix": "tune status"})
                    before = json.loads(out) if ret == 0 else {}
                    c.cfg["mgr_tuner_mode"] = leg   # read LIVE per tick
                    r = await th.tuner_storm(
                        io_cold, io_hot, writes=24, hot_parallel=4,
                        hot_burst=16, ramp_s=1.0)
                    samples[leg].append(r)
                    if leg == "drive" and r.get("tuner"):
                        committed += max(0, r["tuner"].get(
                            "committed", 0) - before.get("committed", 0))
                        reverted += max(0, r["tuner"].get(
                            "reverted", 0) - before.get("reverted", 0))
                    # restore the static config between legs: a
                    # tuner-committed profile must not leak into an
                    # off leg (the operator rm releases its lease)
                    c.cfg["mgr_tuner_mode"] = "off"
                    for ent in ("client.hot", "client.cold"):
                        await c.client.mon_command(
                            {"prefix": "osd client-profile",
                             "op": "rm", "entity": ent})
                    await c.wait_for_clean(timeout=60)
            await cold.shutdown()
            await hot.shutdown()

            def med(leg: str, key: str) -> float:
                return statistics.median(
                    x[key] for x in samples[leg])
            off_p95, drv_p95 = med("off", "cold_p95_s"), \
                med("drive", "cold_p95_s")
            off_agg, drv_agg = med("off", "agg_ops_per_s"), \
                med("drive", "agg_ops_per_s")
            return {
                "off": {"cold_p95_s": round(off_p95, 4),
                        "cold_p99_s": round(
                            med("off", "cold_p99_s"), 4),
                        "agg_ops_per_s": off_agg},
                "drive": {"cold_p95_s": round(drv_p95, 4),
                          "cold_p99_s": round(
                              med("drive", "cold_p99_s"), 4),
                          "agg_ops_per_s": drv_agg},
                "cold_p99_ratio_drive_vs_off": round(
                    med("drive", "cold_p99_s") /
                    max(med("off", "cold_p99_s"), 1e-9), 2),
                "agg_ops_delta_pct": round(
                    (drv_agg - off_agg) / max(off_agg, 1e-9) * 100,
                    1),
                "actions_committed": committed,
                "actions_reverted": reverted,
                # p95 for the verdict (smoke-count p99 is the max);
                # "protects" = no worse for the cold tenant, actions
                # actually landed, throughput not collapsed
                "tuner_protects_cold": bool(
                    drv_p95 <= off_p95 * 1.05 and committed >= 1 and
                    drv_agg >= 0.5 * off_agg),
            }
        finally:
            await c.stop()

    return asyncio.run(run())


def device_resilience_metric() -> dict:
    """Round-16 device-fault resilience plane, two legs:

    (a) **no-fault overhead** — the price of the ``jit_call`` fault
    chokepoint when nothing fires: sweep rate with no injector vs an
    ARMED injector whose device rules never match (the armed path
    pays ``str(key)`` + rule iteration on every device call — exactly
    what production pays while a fault set is installed). The verdict
    the satellite pins: ``resilience_within_noise`` — the armed rate
    stays within noise (<5%) of the bare rate.

    (b) **degrade / re-promote cycle** — one injected kernel-path
    failure on an interpret-mode kernel mapper at
    ``crush_kernel_reprobe_base=0``: wall from the fault to the
    XLA-served answer (the client never errors), and wall back to the
    earned (bit-exact probed) re-promotion."""
    import jax

    from ceph_tpu.bench.crush_sweep import canonical_map, sweep_rate
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.sim import faults as F
    from ceph_tpu.utils import devmon as devmon_mod

    default_pgs = 1 << 20 \
        if jax.devices()[0].platform == "tpu" else 1 << 16
    n_pgs = int(os.environ.get("CEPH_TPU_BENCH_RESIL_PGS",
                               str(default_pgs)))
    mapper = Mapper(canonical_map(1024))
    base = sweep_rate(n_osds=1024, n_pgs=n_pgs, num_rep=3,
                      mapper=mapper)
    inj = F.FaultInjector(seed=16)
    # a device rule that can never match keeps has_device_rules()
    # true, so every jit_call walks the armed slow path
    inj.install("bench_armed",
                [F.jit_fail("bench_no_such_fn", key="never")])
    devmon_mod.set_fault_injector(inj)
    try:
        armed = sweep_rate(n_osds=1024, n_pgs=n_pgs, num_rep=3,
                           mapper=mapper)
    finally:
        devmon_mod.set_fault_injector(None)
    overhead = (base["mappings_per_s"] - armed["mappings_per_s"]) \
        / base["mappings_per_s"] * 100.0
    return {
        "no_fault": {
            "n_pgs": n_pgs,
            "mappings_per_s_bare": base["mappings_per_s"],
            "mappings_per_s_armed": armed["mappings_per_s"],
            "overhead_pct": round(overhead, 2),
            # single-run sweeps jitter a few percent — the flag (not
            # a hard error) records the verdict, loudly
            "resilience_within_noise": bool(overhead < 5.0),
        },
        "fault_cycle": _device_fault_cycle(F, devmon_mod),
    }


def snapshot_metric() -> dict:
    """Round-20 snapshot plane: snap_create and rbd clone wall vs
    image bytes at 1x/8x/64x (each image is ONE data object, so the
    64x row is a 64x-bigger object), plus the first-overwrite-after-
    snap COW cost vs a plain overwrite. Snapshots and clones are
    O(metadata) — a snap cut is a header mutation plus a selfmanaged
    snap id, a clone is a child header pointing at the parent snap,
    and the OSD-side COW is a BlueStore shared-blob ``t.clone`` that
    bumps refcounts instead of copying extents — so NONE of the three
    walls may scale with data size. The claim the section pins:
    ``clone_is_ometa`` — the 64x/1x wall ratio for snap_create, clone
    AND first-overwrite COW overhead all stay far under the 64x data
    ratio (threshold: < 8x)."""
    import asyncio
    import math
    import statistics

    base = int(os.environ.get("CEPH_TPU_BENCH_SNAP_BASE",
                              str(16 << 10)))

    async def one(mult: int) -> dict:
        from ceph_tpu.cluster.vstart import Cluster
        from ceph_tpu.rbd import RBD
        size = base * mult
        order = max(12, math.ceil(math.log2(size)))
        c = await Cluster(n_mons=1, n_osds=3).start()
        try:
            await c.client.pool_create("snapbench", pg_num=8)
            await c.wait_for_clean(timeout=120)
            io = await c.client.open_ioctx("snapbench")
            rbd = RBD(io)
            # plain-overwrite control: same size, never snapped
            await rbd.create("plain", size, order=order)
            plain = await rbd.open("plain")
            await plain.write(0, b"p" * size)
            plain_walls = []
            for i in range(3):
                t0 = time.perf_counter()
                await plain.write(0, bytes([i]) * size)
                plain_walls.append(time.perf_counter() - t0)
            await rbd.create("img", size, order=order)
            img = await rbd.open("img")
            await img.write(0, b"d" * size)
            snap_walls, cow_walls, clone_walls = [], [], []
            for i in range(3):
                t0 = time.perf_counter()
                await img.snap_create(f"s{i}")
                snap_walls.append(time.perf_counter() - t0)
                # first overwrite under the new snap: the OSD clones
                # the head object (shared-blob COW) before applying
                t0 = time.perf_counter()
                await img.write(0, bytes([65 + i]) * size)
                cow_walls.append(time.perf_counter() - t0)
            await img.snap_protect("s0")
            for i in range(3):
                t0 = time.perf_counter()
                await rbd.clone("img", "s0", f"child-{i}")
                clone_walls.append(time.perf_counter() - t0)
            med = statistics.median
            return {"image_bytes": size,
                    "snap_create_ms": round(med(snap_walls) * 1e3, 3),
                    "clone_ms": round(med(clone_walls) * 1e3, 3),
                    "cow_overwrite_ms": round(med(cow_walls) * 1e3, 3),
                    "plain_overwrite_ms": round(
                        med(plain_walls) * 1e3, 3)}
        finally:
            await c.stop()

    async def run() -> dict:
        rows = {f"{m}x": await one(m) for m in (1, 8, 64)}
        r1, r64 = rows["1x"], rows["64x"]

        def ratio(key: str) -> float:
            return round(r64[key] / max(r1[key], 1e-6), 2)
        # the COW verdict compares the COW *overhead* (cow minus
        # plain at the same size): the raw write wall legitimately
        # scales with the payload, the clone it pays must not
        cow_over_1 = max(
            r1["cow_overwrite_ms"] - r1["plain_overwrite_ms"], 1e-3)
        cow_over_64 = max(
            r64["cow_overwrite_ms"] - r64["plain_overwrite_ms"], 0.0)
        cow_ratio = round(cow_over_64 / cow_over_1, 2)
        return {
            "object_bytes_1x": base,
            "rows": rows,
            "snap_create_wall_ratio_64x": ratio("snap_create_ms"),
            "clone_wall_ratio_64x": ratio("clone_ms"),
            "cow_overhead_ratio_64x": cow_ratio,
            "cow_vs_plain_overwrite_1x": round(
                r1["cow_overwrite_ms"] /
                max(r1["plain_overwrite_ms"], 1e-6), 2),
            # the flag — not a hard error — records the verdict
            "clone_is_ometa": bool(
                ratio("snap_create_ms") < 8.0 and
                ratio("clone_ms") < 8.0 and cow_ratio < 8.0),
        }

    return asyncio.run(run())


def multiproc_metric() -> dict:
    """Round 18: the SAME closed-loop client workload against the two
    cluster backends — every daemon in ONE interpreter vs one OS
    process per daemon, over identical localhost-TCP messengers
    (cluster/README.md). The claim the section pins: crossing the
    process boundary (real kernel scheduler, per-process interpreter)
    costs less than 2x in client ops/s (``proc_within_2x`` in the
    compact tail), and proc spawn-to-healthy stays a dev-loop cost
    (seconds, not minutes)."""
    import asyncio

    from ceph_tpu.cluster.vstart import Cluster
    from ceph_tpu.sim.loadgen import LoadGen

    async def one(backend: str) -> dict:
        t0 = time.perf_counter()
        c = await Cluster(n_mons=1, n_osds=3,
                          backend=backend).start()
        spawn_s = time.perf_counter() - t0
        try:
            await c.client.pool_create("mpbench", pg_num=16)
            await c.wait_for_clean(timeout=120)
            rep = await LoadGen(
                c, "mpbench", sessions=200, clients=8,
                ops_per_session=2, write_bytes=512,
                concurrency=64, op_timeout=60.0).run()
            assert rep["errors"] == 0, rep["error_samples"]
            return {"backend": backend,
                    "spawn_to_healthy_s": round(spawn_s, 3),
                    "ops": rep["ops"],
                    "ops_per_s": rep["ops_per_s"],
                    "p50_ms": rep["p50_ms"],
                    "p99_ms": rep["p99_ms"]}
        finally:
            await c.stop()

    async def run() -> dict:
        inproc = await one("inproc")
        proc = await one("proc")
        return {
            "inproc": inproc,
            "proc": proc,
            "ops_ratio_inproc_vs_proc": round(
                inproc["ops_per_s"] / proc["ops_per_s"], 3)
            if proc["ops_per_s"] else None,
            "proc_within_2x":
                proc["ops_per_s"] * 2 >= inproc["ops_per_s"],
        }
    return asyncio.run(run())


def _device_fault_cycle(F, devmon_mod) -> dict:
    """The injected-fault leg: quarantine entry and re-promotion,
    measured on a small interpret-mode kernel mapper (the only
    mapper that HAS a kernel path on CPU; on TPU the same env pin
    keeps the leg's compile cost bounded and deterministic)."""
    import numpy as np

    from ceph_tpu.crush import builder
    from ceph_tpu.crush.builder import TYPE_HOST
    from ceph_tpu.crush.mapper import Mapper

    prev = os.environ.get("CEPH_TPU_CRUSH_KERNEL")
    os.environ["CEPH_TPU_CRUSH_KERNEL"] = "interpret"
    try:
        cm, root = builder.build_hierarchy(4, 2)
        rid = builder.add_simple_rule(cm, root, TYPE_HOST)
        probe = Mapper(cm, config={
            "crush_kernel_reprobe_base": 0.0,
            "crush_kernel_reprobe_max": 0.0,
            "crush_kernel_reprobe_disable_after": 8})
    finally:
        if prev is None:
            os.environ.pop("CEPH_TPU_CRUSH_KERNEL", None)
        else:
            os.environ["CEPH_TPU_CRUSH_KERNEL"] = prev
    xs = np.arange(256)
    out0, path0 = probe.map_pgs_path(rid, xs, 2)
    if path0 != "pallas-interpret":
        return {"skipped": f"no kernel path on this box ({path0})"}
    dm = devmon_mod.devmon()
    before = dm.perf.dump()
    inj = F.FaultInjector(seed=16)
    inj.install("bench_cycle", [
        F.jit_fail("crush_map_pgs", key="*'kern'*", count=1)])
    devmon_mod.set_fault_injector(inj)
    try:
        t0 = time.perf_counter()
        out_deg, path_deg = probe.map_pgs_path(rid, xs, 2)
        degrade_ms = (time.perf_counter() - t0) * 1e3
        served_exact = bool(
            (np.asarray(out_deg) == np.asarray(out0)).all())
        t0 = time.perf_counter()
        path_re, tries = path_deg, 0
        while probe.kernel_quarantine_info() is not None and \
                tries < 50:
            _, path_re = probe.map_pgs_path(rid, xs, 2)
            tries += 1
        repromote_ms = (time.perf_counter() - t0) * 1e3
    finally:
        devmon_mod.set_fault_injector(None)
    after = dm.perf.dump()

    def _delta(k):
        return int(after.get(k, 0)) - int(before.get(k, 0))

    return {
        "kernel_mode": "interpret",
        "degraded_path": path_deg,
        "degraded_served_bit_exact": served_exact,
        "degrade_ms": round(degrade_ms, 2),
        "repromote_ms": round(repromote_ms, 2),
        "repromoted_path": path_re,
        "quarantine_entries": _delta("quarantine_entries"),
        "quarantine_exits": _delta("quarantine_exits"),
        "probes": _delta("quarantine_probes"),
        "faults_injected": _delta("faults_injected"),
    }


def _compile_seconds() -> float:
    """Cumulative jit-compile wall observed by the device-runtime
    monitor (round 14) — the devmon counter every wrapped jit entry
    point (crush mapper/sharded sweep, EC encode/decode/fused-CRC,
    streaming pipeline) feeds on its first call per shape."""
    from ceph_tpu.utils.devmon import devmon
    d = devmon().perf.dump()
    return float(d.get("jit_compile_seconds", 0.0))


def _with_compile_split(fn, *args):
    """Run one bench section and split its wall: the returned dict
    gains ``compile_s`` — the devmon-observed jit compile seconds the
    section spent — so BENCH records can finally distinguish a compile
    regression from a runtime regression (first-call minus warm-call,
    measured rather than inferred)."""
    c0 = _compile_seconds()
    out = fn(*args)
    if isinstance(out, dict):
        out["compile_s"] = round(_compile_seconds() - c0, 3)
        out.update(device_stamp())
    return out


def main() -> None:
    enable_compile_cache()
    c0 = _compile_seconds()
    enc, dec, stream = ec_metrics()
    ec_compile_s = round(_compile_seconds() - c0, 3)
    detail = {
        "seconds_per_step": round(enc["seconds"], 6),
        "batch": enc["batch"],
        "backend": enc["backend"],
        **device_stamp(),
        "mfu_pct": enc.get("mfu_pct"),
        "roofline_GiB/s": enc.get("roofline_GiB/s"),
        "timing": enc.get("timing"),
        "decode_GiB/s": round(dec["GiB/s"], 3),
        "decode_timing_method": dec.get("timing", {}).get("method"),
        "encode_streamed_GiB/s": round(stream["GiB/s"], 4),
        "streamed_note": "H2D inside the loop: host-transfer-bound",
        "retraction": "round-1 value 9317 GiB/s was dispatch-timed and "
                      "invalid; this value is readback-anchored",
    }
    try:
        # resident reference = the headline encode rate; the section
        # re-measures at its own shape when the headline leg crashed
        detail["ec_streaming"] = _with_compile_split(
            ec_streaming_metric, enc.get("GiB/s"))
    except Exception:
        detail["ec_streaming_error"] = _short_err()
    try:
        detail["ec_daemon_path"] = _with_compile_split(
            ec_daemon_path_metric)
    except Exception:
        detail["ec_daemon_path_error"] = _short_err()
    crush = None
    try:
        crush = _with_compile_split(crush_metric)
        detail["crush_mappings_per_s"] = crush["mappings_per_s"]
        detail["crush_detail"] = {
            k: crush[k] for k in ("n_pgs", "n_osds", "num_rep",
                                  "seconds_per_batch", "batch",
                                  "method", "seconds_100M_est",
                                  "path", "path_regressions",
                                  "path_transient",
                                  "fetches_per_sweep",
                                  "fetch_amortization",
                                  "candidate_batched",
                                  "kernel_lanes", "candidate_fold",
                                  "variants", "variants_error")
            if k in crush}
    except Exception:
        detail["crush_error"] = _short_err()
    try:
        detail["crush_multichip"] = _with_compile_split(
            crush_multichip_metric,
            crush["mappings_per_s"] if crush else None)
    except Exception:
        detail["crush_multichip_error"] = _short_err()
    try:
        detail["balancer"] = _with_compile_split(balancer_metric)
    except Exception:
        detail["balancer_error"] = _short_err()
    try:
        detail["mapping_engine"] = _with_compile_split(
            mapping_engine_metric)
    except Exception:
        detail["mapping_engine_error"] = _short_err()
    try:
        detail["mds"] = _with_compile_split(mds_metric)
    except Exception:
        detail["mds_error"] = _short_err()
    try:
        detail["tracing"] = _with_compile_split(tracing_metric)
    except Exception:
        detail["tracing_error"] = _short_err()
    try:
        detail["qos"] = _with_compile_split(qos_metric)
    except Exception:
        detail["qos_error"] = _short_err()
    try:
        detail["telemetry"] = _with_compile_split(telemetry_metric)
    except Exception:
        detail["telemetry_error"] = _short_err()
    try:
        detail["device_resilience"] = _with_compile_split(
            device_resilience_metric)
    except Exception:
        detail["device_resilience_error"] = _short_err()
    try:
        detail["tuning"] = _with_compile_split(tuning_metric)
    except Exception:
        detail["tuning_error"] = _short_err()
    try:
        detail["multiproc"] = _with_compile_split(multiproc_metric)
    except Exception:
        detail["multiproc_error"] = _short_err()
    try:
        detail["snapshot"] = _with_compile_split(snapshot_metric)
    except Exception:
        detail["snapshot_error"] = _short_err()
    print(json.dumps({
        "metric": "ec_encode_k8m3_4MiB",
        "value": round(enc["GiB/s"], 3),
        "unit": "GiB/s",
        "vs_baseline": round(enc["GiB/s"] / BASELINE_GIBS, 3),
        "detail": detail,
    }))
    # Driver-parse line (VERDICT r5 weak #8): the full record above has
    # grown past the driver's tail capture, leaving `parsed: null`.
    # Emit a compact (<500 char) metric/value/unit summary as the LAST
    # stdout line — the driver parses the tail, humans read the blob.
    print(json.dumps(compact_summary(enc, dec, detail)))
    # a section that failed is a failed run, whatever the headline says
    errors = sorted(k for k in detail if k.endswith("_error"))
    if "variants_error" in detail.get("crush_detail", {}):
        errors.append("crush_detail.variants_error")
    if errors:
        print(f"bench: sections failed: {', '.join(errors)}",
              file=sys.stderr)
        sys.exit(1)


def compact_summary(enc: dict, dec: dict, detail: dict) -> dict:
    out = {
        "metric": "ec_encode_k8m3_4MiB",
        "value": round(enc["GiB/s"], 3),
        "unit": "GiB/s",
        "vs_baseline": round(enc["GiB/s"] / BASELINE_GIBS, 3),
        "decode_GiB_s": round(dec["GiB/s"], 3),
    }
    if enc.get("mfu_pct") is not None:
        out["mfu_pct"] = enc["mfu_pct"]
    if detail.get("crush_mappings_per_s") is not None:
        out["crush_mappings_per_s"] = detail["crush_mappings_per_s"]
    elif "crush_error" in detail:
        out["crush_error"] = detail["crush_error"][:120]
    mc = detail.get("crush_multichip")
    if isinstance(mc, dict):
        out["crush_100M_s"] = mc["seconds_100M"]
        out["crush_n_devices"] = mc["n_devices"]
        if mc.get("extrapolated"):
            # a smoke-size rescale must never read as the measured
            # pod wall in the driver-parsed tail
            out["crush_100M_extrapolated"] = True
    regs = detail.get("crush_detail", {}).get("path_regressions")
    if regs:                     # loud in the driver-parsed tail line
        out["crush_path_regression"] = "; ".join(regs)[:120]
    # round 15: the choose_args rate rides the compact tail — the
    # variant the 75.6k/s r05 cliff lived in, so its trajectory must
    # be driver-parsed every round, not buried in the detail blob
    ca = detail.get("crush_detail", {}).get("variants", {})
    if isinstance(ca, dict):
        ca_row = ca.get("choose_args")
        if isinstance(ca_row, dict) and \
                ca_row.get("mappings_per_s") is not None:
            out["crush_choose_args_per_s"] = ca_row["mappings_per_s"]
    qos = detail.get("qos")
    if isinstance(qos, dict):    # the round-11 QoS verdict, compact
        out["qos_protected"] = qos.get("scheduler_protects_cold")
        out["qos_p99_ratio_fifo_vs_mclock"] = [
            qos.get("fifo_p99_ratio"), qos.get("mclock_p99_ratio")]
    tel = detail.get("telemetry")
    if isinstance(tel, dict):    # the round-12 report-loop verdict
        out["telemetry_within_noise"] = tel.get(
            "telemetry_within_noise")
    ecs = detail.get("ec_streaming")
    if isinstance(ecs, dict):    # the round-13 EC aggregator verdict
        out["ec_agg_within_2x"] = ecs.get("ec_agg_within_2x")
        out["ec_agg_GiBs"] = [ecs.get("per_op_GiBs"),
                              ecs.get("aggregated_GiBs"),
                              ecs.get("pipeline_GiBs")]
    ecd = detail.get("ec_daemon_path")
    if isinstance(ecd, dict):    # the round-19 read-side verdict
        out["daemon_within_2x_resident"] = ecd.get(
            "daemon_within_2x_resident")
        out["ec_daemon_GiBs"] = [ecd.get("per_op_GiBs"),
                                 ecd.get("read_agg_GiBs"),
                                 ecd.get("resident_GiBs")]
    res = detail.get("device_resilience")
    if isinstance(res, dict):    # the round-16 fault-plane verdict
        out["resilience_within_noise"] = res.get(
            "no_fault", {}).get("resilience_within_noise")
    tun = detail.get("tuning")
    if isinstance(tun, dict):    # the round-17 self-driving verdict
        out["tuner_protects_cold"] = tun.get("tuner_protects_cold")
        out["tuner_actions"] = [tun.get("actions_committed"),
                                tun.get("actions_reverted")]
    mp = detail.get("multiproc")
    if isinstance(mp, dict):     # the round-18 process-boundary verdict
        out["proc_within_2x"] = mp.get("proc_within_2x")
        out["proc_spawn_s"] = mp.get("proc", {}).get(
            "spawn_to_healthy_s")
    snap = detail.get("snapshot")
    if isinstance(snap, dict):   # the round-20 O(metadata) snap verdict
        out["clone_is_ometa"] = snap.get("clone_is_ometa")
        out["snap_wall_ratios_64x"] = [
            snap.get("snap_create_wall_ratio_64x"),
            snap.get("clone_wall_ratio_64x"),
            snap.get("cow_overhead_ratio_64x")]
    # round 14: total observed jit-compile wall for the whole run —
    # BENCH_r06+ can split a compile regression from a runtime one
    try:
        out["compile_total_s"] = round(_compile_seconds(), 3)
    except Exception:
        pass
    # belt-and-braces: the driver's tail capture is ~2000 chars; stay
    # far inside it even if an error string sneaks in
    while len(json.dumps(out)) > 500 and len(out) > 3:
        out.pop(next(reversed(out)))
    return out


if __name__ == "__main__":
    main()

"""Traffic kind ``rados_bench_rep``: ``rados bench <s> write`` on a
replicated pool.

The loop, the payloads, the window and the summary are ``rados_bench``'s
own (imported, not copied): a closed loop of ``in_flight`` ops, each an
``IoCtx.write_full`` of ``object_size`` bytes to a new object name,
against an in-process cluster in the one process that holds the chip.
What differs is the pool -- ``osd pool create <pool> <pg_num>
replicated``, ``size`` copies, acknowledged at all of them -- and what
is compared, against ``reference/rep_ref.py``.

Keys read from the configuration file: ``mons``, ``osds``, ``pool``,
``size``, ``min_size``, ``pg_num``, ``cluster_config`` (handed to
``Cluster`` over vstart's defaults) and ``crush`` (``osds_per_host``,
``osd_weight``, ``rule``, ``tunables``: the layout the reference maps
on). From the traffic file:
``object_size``, ``in_flight``, ``payload_bases``, ``op_timeout_s``,
``warmup_s``, ``check_every``, ``check_objects``, ``trace``
(``start_s``, ``seconds``), and ``mode``, which has to be ``write``.
Both files may carry a ``rehearsal`` key with the sizes of a CPU
rehearsal.

The device's part of a replicated pool is placement, and steady-state
ops are served from the OSDMap's per-epoch table: the timed loop
launches nothing on the chip, as no ``rados bench`` does. After the
window the comparison asks the client's OSDMap for the pure CRUSH
placement of each compared object's PG on the device
(``pg_to_crush_osds``, the whole-rule kernel). A traced run makes one
such launch more, from the task that starts the profiler and outside
the loop's ops, because the harness cannot reduce a trace in which no
operation ran on a device.

The reference maps on the deployment as the CONFIGURATION states it
(``expected``: a root over one host bucket per ``osds_per_host`` OSDs,
the replicated rule, the pool's size and ``pg_num``), not on what the
cluster built; what the cluster's OSDMap holds (``describe``) is held
against that, part by part.

What is compared, every limit 0: ``replicas_missing`` and
``replicas_differing`` (the stored copies, on the OSDs the reference
names, of ``check_objects`` objects drawn from the seed, the writes in
flight at the window's end among them), ``replicas_misplaced`` (copies
on OSDs the reference does not name, an ``osd map`` answer that is not
the reference's PG and acting set, a device placement that is not the
reference's, a part of the OSDMap's pool or CRUSH map that is not the
configuration's), ``reads_differing``, ``replicas_missing_at_ack`` (one
write in ``check_every``: the stores looked into at the moment
``write_full`` returns), ``ops_failed``, ``device_fallbacks``.
"""

from __future__ import annotations

import asyncio
import json
import resource
import time

import numpy as np

from drivers import rados_bench
from harness import counters
from reference import rep_ref

STEP_NAMES = {1: "take", 2: "choose_firstn", 4: "emit",
              6: "chooseleaf_firstn"}
ALG_NAMES = {5: "straw2"}


class Params:
    """The sizes of a cell as its two files give them, and its payloads:
    what the comparison needs of a cell (the control builds just this)."""

    def __init__(self, config: dict, traffic: dict):
        if traffic["mode"] != "write":
            raise ValueError("rados_bench_rep drives mode 'write' only")
        self.cfg, self.tr = config, traffic
        self.size = int(traffic["object_size"])
        self.copies = int(config["size"])
        self.mode = traffic["mode"]
        self.payloads = None

    make_payloads = rados_bench.Params.make_payloads


TYPE_IDS = {"osd": 0, "host": 1, "root": 10}      # upstream's type ids


def expected(config: dict, pool_id: int) -> tuple[dict, dict, list]:
    """The pool, its CRUSH map and the in/out weights as the
    CONFIGURATION states them, in ``describe``'s form: a root (id -1,
    the first bucket made) over one host bucket per ``osds_per_host``
    OSDs (ids -2, -3, ... in the order the OSDs are added), every OSD
    at ``osd_weight`` and in; the rule with its bucket and type names
    resolved. Only the pool's id is the cluster's to give."""
    cr = config["crush"]
    n, per = int(config["osds"]), int(cr["osds_per_host"])
    w = int(float(cr["osd_weight"]) * 0x10000)
    hosts = [list(range(h, min(h + per, n))) for h in range(0, n, per)]
    buckets = [{"id": -1, "type": TYPE_IDS["root"], "alg": "straw2",
                "items": [-2 - h for h in range(len(hosts))],
                "weights": [w * len(osds) for osds in hosts]}]
    buckets += [{"id": -2 - h, "type": TYPE_IDS["host"], "alg": "straw2",
                 "items": osds, "weights": [w] * len(osds)}
                for h, osds in enumerate(hosts)]
    names = {"default": -1, **TYPE_IDS}
    rule = [[step[0]] + [names.get(a, a) for a in step[1:]]
            + [0] * (3 - len(step)) for step in cr["rule"]]
    if cr["tunables"] != "jewel":
        raise ValueError("the reference has the jewel tunables only")
    desc = {"max_devices": n, "buckets": buckets, "rule": rule,
            "tunables": dict(rep_ref.crush_ref.JEWEL)}
    pool = {"id": pool_id, "pg_num": int(config["pg_num"]),
            "pgp_num": int(config["pg_num"]), "size": int(config["size"]),
            "hashpspool": True}
    return pool, desc, [0x10000] * n


def describe(osdmap, pool_id: int) -> tuple[dict, dict, list]:
    """What the cluster's OSDMap holds, in the same form: read off its
    objects, nothing mapped."""
    from ceph_tpu.osd.types import FLAG_HASHPSPOOL
    pool, crush = osdmap.pools[pool_id], osdmap.crush
    rule = crush.rules[pool.crush_rule]
    desc = {
        "max_devices": int(crush.max_devices),
        "buckets": sorted(({"id": b.id, "type": b.type,
                            "alg": ALG_NAMES.get(b.alg, str(b.alg)),
                            "items": [int(i) for i in b.items],
                            "weights": [int(w) for w in b.weights]}
                           for b in crush.buckets.values()),
                          key=lambda b: -b["id"]),
        "rule": [[STEP_NAMES.get(s.op, str(s.op)), int(s.arg1), int(s.arg2)]
                 for s in rule.steps],
        "tunables": {k: int(getattr(crush.tunables, k))
                     for k in rep_ref.crush_ref.JEWEL},
    }
    pool_desc = {"id": pool.id, "pg_num": pool.pg_num,
                 "pgp_num": pool.pgp_num, "size": pool.size,
                 "hashpspool": bool(pool.flags & FLAG_HASHPSPOOL)}
    return pool_desc, desc, [int(w) for w in osdmap.osd_weight]


def parts_differing(want: tuple, got: tuple) -> list[str]:
    """The parts of the OSDMap's description that are not the
    configuration's: 'pool', 'weights', a key of the CRUSH description."""
    (wp, wc, ww), (gp, gc, gw) = want, got
    out = [k for k in wc if wc[k] != gc.get(k)]
    return out + ["pool"] * (wp != gp) + ["weights"] * (ww != gw)


class Cell(Params, rados_bench.Cell):
    """``rados_bench.Cell``'s loop over a replicated pool. A whole copy
    is what ``one_write`` counts in the stores at the ack."""

    def __init__(self, ctx):
        Params.__init__(self, ctx.config, ctx.traffic)
        self.ctx = ctx
        self.in_flight = int(self.tr["in_flight"])
        self.timeout = float(self.tr.get("op_timeout_s", 120.0))
        self.prefix = "benchmark_data_"
        self.cluster = self.io = self.victim = None
        self.ops = []                    # (index, t0, t1, ok)
        self.at_ack = {}                 # index -> whole copies at ack
        self.placed = {}                 # index -> the device's OSDs
        self.kept = {}
        self.t_open = None
        self.shard_len = self.size       # a copy is the whole object
        self.k = self.m = None           # no code: summarise() notes them
        self.pool_id = None

    # -- set-up -------------------------------------------------------------
    async def bring_up(self) -> None:
        from ceph_tpu.cluster.vstart import Cluster
        ctx, cfg = self.ctx, self.cfg
        with ctx.phase("cluster"):
            c = self.cluster = await Cluster(
                n_mons=int(cfg["mons"]), n_osds=int(cfg["osds"]),
                config=dict(cfg["cluster_config"])).start()
            ctx.osds = c.osds
            await self.mon({"prefix": "osd pool create", "pool": cfg["pool"],
                            "pg_num": int(cfg["pg_num"]),
                            "pool_type": "replicated",
                            "size": self.copies,
                            "min_size": int(cfg["min_size"])})
            await c.wait_for_clean(timeout=300)
            self.io = await c.client.open_ioctx(cfg["pool"])
            self.pool_id = self.io.pool_id
        with ctx.phase("payloads"):
            self.make_payloads(ctx.seed)

    def device_placement(self, oid: str) -> list[int]:
        """The OSDs CRUSH gives the object's PG, from the client's
        OSDMap through the mapper on the device: not the per-epoch
        table that serves the ops."""
        om = self.cluster.client.monc.osdmap
        pool = om.pools[self.pool_id]
        seed = pool.raw_pg_to_pg(
            np.asarray([pool.hash_key(oid)], dtype=np.uint32), xp=np)
        raw, _pps = om.pg_to_crush_osds(self.pool_id, seed)
        return [int(o) for o in raw[0]]

    async def traced_stretch(self, t_open: float, plan) -> None:
        """``rados_bench``'s, with one placement on the device once the
        profiler runs: the loop's ops launch nothing, and a trace with
        no device operation cannot be reduced."""
        await asyncio.sleep(max(0.0, t_open + plan[0] - time.perf_counter()))
        self.ctx.trace_start()
        self.placed[0] = self.device_placement(self.name(0))
        await asyncio.sleep(plan[1])
        self.ctx.trace_stop()

    # -- the comparison -----------------------------------------------------
    async def gather(self, objects: list[int]) -> list[dict]:
        """What the program holds and returns for the given objects:
        its own PG and acting set (``osd map``), the copy in every OSD
        store that holds the object, and the bytes a client reads."""
        answers = []
        for i in objects:
            oid = self.name(i)
            info = json.loads(await self.mon(
                {"prefix": "osd map", "pool": self.cfg["pool"],
                 "object": oid}))
            stored = {}
            for o in self.cluster.osds:
                for cid, coll in o.store.colls.items():
                    if oid in coll:
                        stored[o.whoami] = (
                            cid, rados_bench.stored_shard(o, cid, oid))
            try:
                back = await self.io.read(oid, timeout=self.timeout)
            except Exception as e:
                self.ctx.log(f"read-back of {oid} failed: {e}")
                back = None
            answers.append({"object": i, "pgid": info["pgid"],
                            "acting": info["acting"], "stored": stored,
                            "read": back})
        return answers


def compare_answers(payloads, pool: rep_ref.Pool, name,
                    answers: list[dict]) -> dict:
    """The answers against the plain reference, all exact (limit 0):
    copies the reference's OSDs lack, copies there that are not the
    bytes written, copies anywhere else or a PG or acting set that is
    not the reference's, reads that differ from the bytes written."""
    out = dict(replicas_missing=0, replicas_differing=0,
               replicas_misplaced=0, reads_differing=0)
    for a in answers:
        oid = name(a["object"])
        want = payloads.get(a["object"])
        acting, pgid = pool.acting(oid), pool.pgid(oid)
        for osd, copy in zip(acting, rep_ref.replicas(want, len(acting))):
            cid, got = a["stored"].get(osd, (None, None))
            if got is None or cid != pgid:
                out["replicas_missing"] += 1
            elif got != copy:
                out["replicas_differing"] += 1
        out["replicas_misplaced"] += sum(
            1 for osd in a["stored"] if osd not in acting)
        if a["pgid"] != pgid or list(a["acting"]) != acting \
                or len(acting) != pool.size:
            out["replicas_misplaced"] += 1
        if a["read"] != want:
            out["reads_differing"] += 1
    return out


def compare(ctx, p: Params, pool: rep_ref.Pool, name, answers, at_ack,
            placed, map_differs=()) -> None:
    """Every number that decides ``correct`` for this kind of traffic.
    ``at_ack``: {op: whole copies in the stores at the moment of the
    ack}; ``placed``: {object: the device's placement of its PG};
    ``map_differs``: ``parts_differing`` of the cluster's OSDMap."""
    found = compare_answers(p.payloads, pool, name, answers)
    found["replicas_missing_at_ack"] = sum(
        p.copies - n for n in at_ack.values())
    found["replicas_misplaced"] += len(map_differs) + sum(
        1 for i, osds in placed.items() if osds != pool.acting(name(i)))
    for key, value in found.items():
        ctx.compared.add(key, value, 0)
    ctx.compared.add("ops_failed", ctx.failed, 0)
    ctx.compared.add("device_fallbacks", counters.fallbacks(ctx.delta), 0)


def rep_counters(osds) -> dict:
    """The OSDs' fan-out counters, added up (0 where a program from
    before them has none)."""
    out = {"rep_ops": 0, "rep_fanout_bytes": 0}
    for o in osds:
        dump = o.perf.dump()
        for k in out:
            out[k] += dump.get(k, 0)
    return out


RUSAGE = {"ru_utime": "loop_user_s", "ru_stime": "loop_sys_s",
          "ru_minflt": "loop_page_faults", "ru_nivcsw": "loop_preempted"}


def health_at_open(cell: Cell) -> dict:
    lead = cell.cluster.leader()
    return {"epoch": lead.osdmon.osdmap.epoch,
            "log_seq": lead.logmon.last_seq(),
            "slow": len(lead.osdmon.slow_osds),
            "rusage": resource.getrusage(resource.RUSAGE_THREAD)}


def log_health(ctx, cell: Cell, at_open: dict) -> None:
    """What the failure detector and the host made of the window, for
    the log: map epochs that passed, OSDs the map shows down or the mon
    takes for slow, accusations the mon holds, the cluster log's
    warnings (``ceph log last``) since the window opened, and the
    loop's thread as the kernel saw it."""
    lead = cell.cluster.leader()
    om = lead.osdmon.osdmap
    warned = [e["msg"] for e in lead.logmon.tail(200)
              if e["seq"] > at_open["log_seq"] and e["level"] != "INF"]
    ctx.obs.update(
        osdmap_epochs_in_window=om.epoch - at_open["epoch"],
        osds_down_at_close=sum(
            1 for o in cell.cluster.osds if not bool(om.is_up(o.whoami))),
        failure_reports_at_close=sum(
            len(v) for v in lead.osdmon.failure_reporters.values()),
        osds_slow_at_open=at_open["slow"],
        osds_slow_at_close=len(lead.osdmon.slow_osds),
        cluster_log_warnings=len(warned))
    for msg in warned[:8]:
        ctx.log(f"cluster log: {msg}")
    # what the host did to the loop's thread: its user and system CPU,
    # the pages it faulted in and the times it was taken off its core
    now = resource.getrusage(resource.RUSAGE_THREAD)
    for field, key in RUSAGE.items():
        ctx.obs[key] = round(
            getattr(now, field) - getattr(at_open["rusage"], field), 3)


async def _run(ctx) -> None:
    cell = Cell(ctx)
    tr = ctx.traffic
    rng = np.random.default_rng(ctx.seed + 2)
    try:
        await cell.bring_up()
        cell.start_loop(rng, int(tr.get("check_every", 4)), 0)
        with ctx.phase("compile_warmup"):
            # the placement program of a one-PG batch, then the loop
            cell.device_placement(cell.name(0))
            await cell.run_until(time.perf_counter()
                                 + float(tr.get("warmup_s", 10.0)))
        plan = ctx.trace_plan()
        rep_open = rep_counters(cell.cluster.osds)
        health = health_at_open(cell)
        t_open = cell.t_open = ctx.open_window()
        tracer = asyncio.ensure_future(cell.traced_stretch(t_open, plan)) \
            if plan else None
        await cell.run_until(t_open + ctx.seconds)
        ctx.close_window(t_open)
        rep_close = rep_counters(cell.cluster.osds)
        log_health(ctx, cell, health)
        await cell.drain()
        if tracer is not None:
            await tracer
        rados_bench.summarise(ctx, cell, t_open)
        ctx.log("device programs first called before the window: "
                f"{int(ctx.at_open['devmon.jit_compiles'])}, "
                f"{ctx.at_open['devmon.jit_compile_seconds']:.1f}s in all")
        for key in ("k", "m", "agg_family", "traced_ec_bytes"):
            ctx.obs.pop(key, None)       # an EC cell's, nothing here
        ctx.obs.update({k: rep_close[k] - rep_open[k] for k in rep_close})
        ctx.reduce_trace()
        t0 = time.perf_counter()
        t_end = t_open + ctx.seconds
        late = [op[0] for op in cell.ops if op[2] > t_end and op[3]]
        done = [op[0] for op in cell.ops if op[2] > t_open and op[3]]
        objects = rados_bench.pick_objects(
            done, late, int(tr.get("check_objects", 32)), ctx.seed)
        answers = await cell.gather(objects)
        for i in objects:
            cell.placed[i] = cell.device_placement(cell.name(i))
        want = expected(ctx.config, cell.pool_id)
        differs = parts_differing(want, describe(
            cell.cluster.leader().osdmon.osdmap, cell.pool_id))
        if differs:
            ctx.log(f"the OSDMap is not the configuration's in: {differs}")
        compare(ctx, cell, rep_ref.Pool(*want), cell.name, answers,
                cell.at_ack, cell.placed, differs)
        ctx.obs.update(acks_checked=len(cell.at_ack),
                       placements_checked=len(cell.placed),
                       objects_checked=len(objects))
        ctx.log(f"comparison: {len(objects)} objects in "
                f"{time.perf_counter() - t0:.2f}s")
    finally:
        await cell.close()


def run(ctx) -> None:
    asyncio.run(_run(ctx))

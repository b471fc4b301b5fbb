"""Traffic kind ``rados_bench``: what ``rados bench`` does to a pool.

A closed loop of ``in_flight`` ops of ``object_size`` bytes against an
in-process cluster in the one process that holds the chip, through
``IoCtx.write_full`` (``mode: write``, each op a new object name, as
``rados bench <s> write``) or ``IoCtx.read`` of objects drawn uniformly
from a pre-filled population (``mode: rand``, as ``rados bench <s>
rand``). The loop is ``ceph_tpu/bench/rados_cli._bench``'s; the payloads
are made from the seed and differ op by op, and every latency is kept.
``degraded: true`` kills one OSD after the pre-fill and leaves it down
and in, so that reads of objects that lack a data shard are decoded.

What is compared, once the window has closed: for a sample of the
objects drawn from the seed, the k+m shards in the OSDs' stores against
the plain Reed-Solomon reference's shards of those bytes, and what the
client reads back against the bytes written; for the writes a sample
checked at the moment of the ack, that all k+m shards were in their
stores then; in a degraded cell, the lost shard rebuilt by the
reference from the stored survivors.
"""

from __future__ import annotations

import asyncio
import json
import struct
import time

import numpy as np

from harness import counters
from harness.stats import percentile
from reference import rs_ref

MIB = 1 << 20


class Payloads:
    """Object i's bytes: a 16-byte stamp (seed, i) and the rest of one
    of a few random bases made from the seed. All differ."""

    def __init__(self, seed: int, size: int, bases: int):
        rng = np.random.default_rng(seed)
        self.seed, self.size = seed, size
        self.bases = [rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                      for _ in range(bases)]

    def get(self, i: int) -> bytes:
        base = self.bases[i % len(self.bases)]
        stamp = struct.pack("<QQ", self.seed & (2**64 - 1), i)
        return stamp + base[16:] if self.size >= 16 else base


def shard_holders(osds, oid: str) -> dict:
    """{osd id: stored bytes length} over every OSD store that holds a
    shard of the object now. Reads the stores in place: MemStore keeps
    collections of objects by name."""
    held = {}
    for o in osds:
        for coll in o.store.colls.values():
            obj = coll.get(oid)
            if obj is not None:
                held[o.whoami] = len(obj.data)
                break
    return held


def stored_shard(osd, cid: str, oid: str):
    coll = osd.store.colls.get(cid)
    obj = coll.get(oid) if coll is not None else None
    return None if obj is None else bytes(obj.data)


class Params:
    """The sizes of a cell as its two files give them, and its payloads:
    what the comparison needs of a cell (the control builds just this)."""

    def __init__(self, config: dict, traffic: dict):
        self.cfg, self.tr = config, traffic
        self.size = int(traffic["object_size"])
        self.k, self.m = int(config["k"]), int(config["m"])
        self.unit = int(config["stripe_unit"])
        self.mode = traffic["mode"]
        self.payloads = None

    def make_payloads(self, seed: int) -> None:
        self.payloads = Payloads(seed, self.size,
                                 int(self.tr.get("payload_bases", 8)))


class Cell(Params):
    def __init__(self, ctx):
        super().__init__(ctx.config, ctx.traffic)
        self.ctx = ctx
        self.in_flight = int(self.tr["in_flight"])
        self.timeout = float(self.tr.get("op_timeout_s", 120.0))
        self.prefix = "benchmark_data_"
        self.cluster = self.io = None
        self.victim = None
        self.ops = []                    # (index, t0, t1, ok)
        self.at_ack = {}                 # index -> shards held at ack
        self.kept = {}                   # op number -> (object, bytes)
        self.t_open = None               # set when the window opens
        self.shard_len = -(-self.size // (self.k * self.unit)) * self.unit

    # -- set-up -------------------------------------------------------------
    async def bring_up(self) -> None:
        from ceph_tpu.cluster.vstart import Cluster
        ctx, cfg = self.ctx, self.cfg
        with ctx.phase("cluster"):
            c = self.cluster = await Cluster(
                n_mons=int(cfg["mons"]), n_osds=int(cfg["osds"]),
                config=dict(cfg["cluster_config"])).start()
            ctx.osds = c.osds
            await self.mon({"prefix": "osd erasure-code-profile set",
                            "name": "bench-profile",
                            "profile": list(cfg["profile"])})
            await self.mon({"prefix": "osd pool create", "pool": cfg["pool"],
                            "pg_num": int(cfg["pg_num"]),
                            "pool_type": "erasure",
                            "erasure_code_profile": "bench-profile"})
            await c.wait_for_clean(timeout=300)
            self.io = await c.client.open_ioctx(cfg["pool"])
        with ctx.phase("payloads"):
            self.make_payloads(ctx.seed)

    async def mon(self, cmd: dict):
        ret, rs, out = await self.cluster.client.mon_command(cmd)
        if ret != 0:
            raise RuntimeError(f"mon command {cmd.get('prefix')!r}: {rs}")
        return out

    def name(self, i: int) -> str:
        return f"{self.prefix}{i}"

    def warm_encode_shapes(self) -> None:
        """Every primary PG keeps a plugin instance of its own and every
        instance compiles each batch shape for itself, so the warm-up
        enumerates them: one op's stripes times 1, 2, 4, ... up to
        ``warm_max_ops`` coalesced ops (the aggregator pads a batch to
        a power of two). What a later refactor takes away here is found
        by ``window_compiles``."""
        import jax
        stripes = self.shard_len // self.unit
        top = int(self.tr.get("warm_max_ops", 8))
        shapes = [stripes * (1 << p) for p in range(top.bit_length())
                  if (1 << p) <= top]
        n = 0
        for o in self.cluster.osds:
            for pg in o.pgs.values():
                ec = getattr(pg, "ec", None)
                if ec is None or not pg.is_primary():
                    continue
                for b in shapes:
                    z = np.zeros((b, self.k, self.unit), dtype=np.uint8)
                    jax.block_until_ready(ec.encode_batch_with_crc(z))
                    n += 1
        self.ctx.log(f"warmed {n} (plugin instance, batch shape) pairs: "
                     f"shapes {shapes}")

    async def prefill(self, n: int) -> None:
        sem = asyncio.Semaphore(int(self.tr.get("prefill_in_flight", 16)))

        async def put(i):
            async with sem:
                await self.io.write_full(self.name(i), self.payloads.get(i),
                                         timeout=self.timeout)
        await asyncio.gather(*[put(i) for i in range(n)])

    def placement(self) -> tuple[dict, dict]:
        """(collection of every object, acting set of every PG) as the
        OSDs hold them now."""
        acting, where = {}, {}
        for o in self.cluster.osds:
            for pg in o.pgs.values():
                if getattr(pg, "ec", None) is not None and pg.is_primary():
                    acting[pg.cid] = [int(a) for a in pg.acting]
            for cid, coll in o.store.colls.items():
                for oid in coll:
                    if oid.startswith(self.prefix):
                        where[oid] = cid
        return where, acting

    async def degrade(self, n_objects: int) -> None:
        """Kill the OSD that holds a data shard of the median number of
        objects (the same one whatever the seed: placement does not
        depend on it), and wait until the map says it is down. It stays
        in, so nothing backfills."""
        where, acting = self.placement()
        self.where, self.acting = where, acting
        per_osd = {o.whoami: 0 for o in self.cluster.osds}
        for i in range(n_objects):
            a = acting[where[self.name(i)]]
            for osd in a[:self.k]:
                per_osd[osd] += 1
        ranked = sorted(per_osd, key=lambda o: (per_osd[o], o))
        self.victim = ranked[len(ranked) // 2]
        await self.cluster.kill_osd(self.victim)
        await self.cluster.wait_for_osd_down(self.victim, timeout=120)
        self.lacks_data = {
            i for i in range(n_objects)
            if self.victim in acting[where[self.name(i)]][:self.k]}
        self.ctx.log(f"osd.{self.victim} down: {len(self.lacks_data)} of "
                     f"{n_objects} objects lack a data shard")

    # -- the closed loop ----------------------------------------------------
    async def one_write(self, i: int, check_ack: bool) -> None:
        data = self.payloads.get(i)
        t0 = time.perf_counter()
        ok = True
        try:
            with self.ctx.annotate("write_full"):
                await self.io.write_full(self.name(i), data,
                                         timeout=self.timeout)
        except Exception as e:          # a failed op is counted, not raised
            ok = False
            self.ctx.log(f"write {i} failed: {type(e).__name__}: {e}")
        t1 = time.perf_counter()
        if ok and check_ack:
            # no await since the ack: what the stores hold NOW is what
            # they held when the write was acknowledged
            held = shard_holders(self.cluster.osds, self.name(i))
            self.at_ack[i] = sum(1 for n in held.values()
                                 if n == self.shard_len)
        self.ops.append((i, t0, t1, ok))

    async def one_read(self, n: int, obj: int, keep: bool) -> None:
        t0 = time.perf_counter()
        ok = True
        try:
            with self.ctx.annotate("read"):
                data = await self.io.read(self.name(obj),
                                          timeout=self.timeout)
            if keep and self.t_open is not None:
                self.kept[n] = (obj, data)
            elif len(data) != self.size:
                ok = False
        except Exception as e:
            ok = False
            self.ctx.log(f"read {obj} failed: {type(e).__name__}: {e}")
        self.ops.append((obj, t0, time.perf_counter(), ok))

    def start_loop(self, rng, sample_every: int, population: int) -> None:
        self._pending, self._next = set(), 0
        self._rng, self._every, self._pop = rng, sample_every, population

    async def run_until(self, stop: float) -> None:
        """The rados bench loop up to host-clock time ``stop``: keep
        ``in_flight`` ops going. What is in flight at ``stop`` stays in
        flight: the warm-up runs into the window with the pipeline
        full, as a bench that has been going for a while."""
        pending, rng = self._pending, self._rng
        while time.perf_counter() < stop:
            while len(pending) < self.in_flight \
                    and time.perf_counter() < stop:
                sampled = int(rng.integers(self._every)) == 0
                if self.mode == "write":
                    coro = self.one_write(self._next, sampled)
                else:
                    coro = self.one_read(self._next,
                                         int(rng.integers(self._pop)),
                                         sampled)
                pending.add(asyncio.ensure_future(coro))
                self._next += 1
            _done, self._pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED)
            pending = self._pending

    async def drain(self) -> None:
        if self._pending:
            await asyncio.wait(self._pending)
            self._pending = set()

    async def warm_every_pg(self) -> None:
        """One read of an object of every PG, one that lacks a data
        shard where the PG has one, so that each PG's decode program
        has run once before the window."""
        first = {}
        for i in range(self._pop):
            cid = self.where[self.name(i)]
            if cid not in first or (first[cid] not in self.lacks_data
                                    and i in self.lacks_data):
                first[cid] = i
        sem = asyncio.Semaphore(self.in_flight)

        async def get(i):
            async with sem:
                await self.io.read(self.name(i), timeout=self.timeout)
        await asyncio.gather(*[get(i) for i in first.values()])

    async def traced_stretch(self, t_open: float, plan) -> None:
        await asyncio.sleep(max(0.0, t_open + plan[0] - time.perf_counter()))
        self.ctx.trace_start()
        await asyncio.sleep(plan[1])
        self.ctx.trace_stop()

    # -- the comparison -----------------------------------------------------
    async def gather(self, objects: list[int]) -> list[dict]:
        """What the program holds and returns for the given objects:
        the stored shard at each position of the acting set (None where
        a store lacks it, absent where the OSD is down), the position
        lost with the down OSD, and the bytes a client reads back."""
        answers = []
        for i in objects:
            oid = self.name(i)
            info = json.loads(await self.mon(
                {"prefix": "osd map", "pool": self.cfg["pool"],
                 "object": oid}))
            cid = info["pgid"]
            acting = self.acting[cid] if self.victim is not None \
                else info["acting"]
            stored = {pos: stored_shard(self.cluster.osds[osd], cid, oid)
                      for pos, osd in enumerate(acting)
                      if osd != self.victim}
            lost = acting.index(self.victim) \
                if self.victim in acting else None
            try:
                back = await self.io.read(oid, timeout=self.timeout)
            except Exception as e:
                self.ctx.log(f"read-back of {oid} failed: {e}")
                back = None
            answers.append({"object": i, "stored": stored, "lost": lost,
                            "read": back})
        return answers

    async def close(self) -> None:
        if self.cluster is not None:
            await self.cluster.stop()


def compare_answers(payloads: Payloads, k: int, m: int, unit: int,
                    answers: list[dict]) -> dict:
    """The answers against the plain reference: how many stored shards
    are missing or are not the reference's shard of the bytes written,
    how many lost shards the reference cannot rebuild from the stored
    survivors to what it should be, how many reads differ from the
    bytes written. All exact: each is compared with the limit 0."""
    out = dict(shards_missing=0, shards_differing=0, rebuilt_differing=0,
               reads_differing=0)
    for a in answers:
        want = payloads.get(a["object"])
        ref = rs_ref.shards(want, k, m, unit)
        have = {}
        for pos, got in a["stored"].items():
            if got is None:
                out["shards_missing"] += 1
            elif got != ref[pos]:
                out["shards_differing"] += 1
            else:
                have[pos] = got
        lost = a["lost"]
        if lost is not None and len(have) >= k and \
                rs_ref.reconstruct(have, lost, k, m) != ref[lost]:
            out["rebuilt_differing"] += 1
        if a["read"] != want:
            out["reads_differing"] += 1
    return out


def compare(ctx, p: Params, answers, at_ack, kept) -> None:
    """Every number that decides ``correct`` for this kind of traffic.
    ``at_ack``: {op: shards held in the stores at the moment of the
    ack}; ``kept``: [(object, bytes a window read returned)]."""
    found = compare_answers(p.payloads, p.k, p.m, p.unit, answers)
    if p.mode == "write":
        found["shards_missing_at_ack"] = sum(
            p.k + p.m - n for n in at_ack.values())
    found["reads_differing"] += sum(
        1 for obj, data in kept if data != p.payloads.get(obj))
    for name, value in found.items():
        ctx.compared.add(name, value, 0)
    ctx.compared.add("ops_failed", ctx.failed, 0)
    ctx.compared.add("device_fallbacks", counters.fallbacks(ctx.delta), 0)


def pick_objects(candidates: list[int], always: list[int], want: int,
                 seed: int) -> list[int]:
    """``want`` objects: those of ``always`` first (the writes that were
    in flight when the window closed), the rest drawn from the seed."""
    rng = np.random.default_rng(seed + 1)
    chosen = list(dict.fromkeys(always))[:want]
    rest = [c for c in candidates if c not in set(chosen)]
    for j in rng.permutation(len(rest))[:max(0, want - len(chosen))]:
        chosen.append(rest[int(j)])
    return chosen


def summarise(ctx, cell: Cell, t_open: float) -> None:
    """End-to-end values and the driver's timings from the op records:
    the ops that returned inside the window count, with their whole
    latency; the ones in flight at its end were awaited and are
    checked, not counted."""
    t_end = t_open + ctx.seconds
    inside = [op for op in cell.ops if t_open < op[2] <= t_end]
    good = [op for op in inside if op[3]]
    after = [op for op in cell.ops if op[2] > t_end]
    failed = sum(1 for op in inside + after if not op[3])
    ctx.attempted = len(inside) + sum(1 for op in after if not op[3])
    ctx.failed = failed
    lat = [(t1 - t0) if ok else cell.timeout
           for _i, t0, t1, ok in inside]
    ctx.values["client_mib_s"] = len(good) * cell.size / MIB / ctx.seconds
    ctx.values["op_p95_ms"] = percentile(lat, 95) * 1e3 if lat else None
    ctx.obs.update(
        op_lat_s=lat, ops_in_window=len(inside),
        ops_in_flight_at_end=len(after),
        ops_per_5s=" ".join(str(sum(
            1 for op in good if b <= op[2] - t_open < b + 5))
            for b in range(0, int(ctx.seconds), 5)),
        k=cell.k, m=cell.m,
        resident_entries_at_open=int(ctx.at_open["resident.entries"]),
        agg_family="agg" if cell.mode == "write" else "read_agg")
    if ctx.trace_span is not None:
        a, b = ctx.trace_span
        traced = [op for op in good if a <= op[2] <= b]
        if cell.mode == "write":
            ec = len(traced) * cell.size
        else:
            ec = sum(cell.size for op in traced if op[0] in cell.lacks_data)
        ctx.obs.update(traced_ops=len(traced), traced_ec_bytes=ec)


async def _run(ctx) -> None:
    cell = Cell(ctx)
    tr = ctx.traffic
    rng = np.random.default_rng(ctx.seed + 2)
    try:
        await cell.bring_up()
        population = int(tr.get("population", 0))
        if cell.mode == "rand":
            with ctx.phase("prefill"):
                await cell.prefill(population)
            if tr.get("degraded"):
                with ctx.phase("degrade"):
                    await cell.degrade(population)
        keep_every = int(tr.get("check_every", 4))
        cell.start_loop(rng, keep_every, population)
        with ctx.phase("compile_warmup"):
            if cell.mode == "write":
                cell.warm_encode_shapes()
            elif tr.get("degraded"):
                await cell.warm_every_pg()
            await cell.run_until(time.perf_counter()
                                 + float(tr.get("warmup_s", 3.0)))
        plan = ctx.trace_plan()
        t_open = cell.t_open = ctx.open_window()
        tracer = asyncio.ensure_future(cell.traced_stretch(t_open, plan)) \
            if plan else None
        await cell.run_until(t_open + ctx.seconds)
        ctx.close_window(t_open)
        await cell.drain()
        if tracer is not None:
            await tracer
        summarise(ctx, cell, t_open)
        ctx.reduce_trace()
        t0 = time.perf_counter()
        want = int(tr.get("check_objects", 32))
        t_end = t_open + ctx.seconds
        if cell.mode == "write":
            late = [op[0] for op in cell.ops if op[2] > t_end and op[3]]
            done = [op[0] for op in cell.ops if op[2] > t_open and op[3]]
            objects = pick_objects(done, late, want, ctx.seed)
        else:
            objects = pick_objects(list(range(population)), [], want,
                                   ctx.seed)
        answers = await cell.gather(objects)
        compare(ctx, cell, answers, cell.at_ack, list(cell.kept.values()))
        ctx.obs.update(acks_checked=len(cell.at_ack),
                       reads_compared=len(cell.kept))
        ctx.obs["objects_checked"] = len(objects)
        ctx.log(f"comparison: {len(objects)} objects in "
                f"{time.perf_counter() - t0:.2f}s")
    finally:
        await cell.close()


def run(ctx) -> None:
    asyncio.run(_run(ctx))

"""OSD-side EC aggregators: one windowed device batcher, two directions.

The EC kernels hit their resident rate only on deep batches, but every
client op used to launch its own ``encode_batch`` (from
``ECPG._submit_ec_write`` / ``_rebuild_shard`` / the backfill-push
builder) or ``decode_batch`` (a degraded read, a recovery rebuild and
a backfill push all end in ``ECPG._gather``), so at production traffic
the data path is dispatch-bound, not compute-bound — the read side
exactly during repair churn, when an OSD dies and every PG it touched
rebuilds while clients keep reading. ``_WindowedBatcher`` coalesces
concurrent launches from ALL the PGs on one OSD into a single padded
batched kernel launch per flush window, amortizing dispatch like the
CRUSH sharded sweep amortizes mapping (PR 10). ``ECAggregator``
(encode) and ``ECReadAggregator`` (decode/repair) are its two
directions; each holds only what is its own.

Contract (pinned in tests/test_ec_agg.py and tests/test_ec_read_agg.py):

- **bit-exact**: every EC kernel is stripe-row-independent, so the
  concatenated batch's rows equal the per-op results lane for lane;
  the per-op path survives as the measured baseline behind
  ``osd_ec_agg=off`` / ``osd_ec_read_agg=off`` (read LIVE);
- **latency-bounded**: a batch flushes when ``<prefix>_window_us``
  expires, when ``<prefix>_max_stripes`` accumulate, or when the queue
  goes IDLE (one event-loop yield plus a window slice with no new
  arrivals) — a lone op is never held past the window;
- **padded launches**: the batch is zero-padded to the next power of
  two before dispatch, so the jit cache sees O(log max_batch) shapes
  per group instead of one program per concurrency level;
- **degrade ladder** (round 16): a failed batch flush disaggregates
  per-op, each op gets ``osd_ec_fallback_retries`` more device
  attempts, then the bit-exact host reference; only the op that still
  fails sees the exception;
- **fused checksum** (encode): when any waiter wants write-time
  ``_hcrc`` stamps, the flush runs the plugin's fused checksum+encode
  program (ec/jax_plugin.encode_batch_with_crc), so checksum+encode
  stays ONE device launch for the whole coalesced batch;
- **QoS-honest** (decode): repair decodes (rebuild/backfill — not
  client degraded reads, already cost-tagged at admission) charge a
  recovery-class grant at the bytes/osd_qos_cost_per_io_bytes divisor
  client writes pay, so repair churn can't starve cold tenants.

The ladders are NOT symmetric (a named debt in ROADMAP.md): repeated
failures quarantine the whole device DECODE on exponential backoff,
ops served by the reference meanwhile; the encode side quarantines only
the fused checksum program and retries a dead device op by op.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

import numpy as np

from ceph_tpu.utils import tracing
from ceph_tpu.utils.logging import get_logger
from ceph_tpu.utils.perf_counters import PerfCountersBuilder

log = get_logger("osd")


@dataclass(slots=True, eq=False)
class _Entry:
    """One queued op: its rows and what it asked of the launch beyond
    them (encode: whether it wants row CRCs; decode: nothing)."""
    data: np.ndarray
    ask: bool
    fut: asyncio.Future
    t0: float
    span: object = None         # the op's ec.agg_wait interval


@dataclass(slots=True, eq=False)
class _Group:
    """One in-flight coalescing batch; staleness is decided by
    identity (``self._groups.get(key) is g``), never by counters.
    ``head``: the launch's arguments before the rows — ``(ec,)`` to
    encode, ``(ec, want, avail)`` to decode."""
    head: tuple
    entries: list[_Entry] = field(default_factory=list)
    stripes: int = 0
    task: asyncio.Task | None = None


class _WindowedBatcher:
    """The coalescing policy, once for both directions: enqueue, the
    window / idle / full flush, the degrade skeleton, padding, drain
    and the shared counters. Nothing of an OSD is held beyond
    ``config`` and ``tracer``. A direction supplies the attributes
    below and three methods: ``_launch(head, data, ask, pad=True,
    ctx=None)``, one device launch over ``data`` (its own ``_run``;
    unpadded, an op's own result); ``_cut(out, lo, hi, ask)``, rows
    ``lo:hi`` of a batch's result as one op gets them;
    ``_reference(head, data)``, the op's result from the bit-exact host
    reference. Where it has a device quarantine it also overrides
    ``_device_resting`` / ``_device_failed``."""

    OPT = ""                    # "osd_ec_agg" | "osd_ec_read_agg"
    VERB = ""                   # "encode" | "decode"
    EXTRA_COUNTERS: tuple = ()  # ((name, description), ...)
    DUMP_EXTRA: tuple = ()      # counters dump() shows besides the shared

    def __init__(self, config: dict | None = None, tracer=None):
        self.config = config if config is not None else {}
        self.tracer = tracer    # the owning daemon's, for the sections
        self.name = self.OPT.removeprefix("osd_")
        self.perf = self._build_perf()
        self._groups: dict[tuple, _Group] = {}
        self.stopped = False

    def _build_perf(self):
        """Per-OSD counter family (register=False: several in-process
        OSDs each own one; they reach prometheus through the PR 12
        daemon->mgr report path as ``ceph_<family>_*`` rows, not the
        process-local singleton collection)."""
        v = self.VERB
        b = (PerfCountersBuilder(self.OPT)
             .add_u64_counter("batches", f"coalesced {v} launches")
             .add_u64_counter("stripes", f"stripes {v}d through batches")
             .add_u64_counter("ops", f"{v} requests served")
             .add_u64_counter("bypass",
                              f"{v}s served per-op ({self.OPT}=off)")
             .add_u64_counter("flush_window",
                              "flushes triggered by the window expiring")
             .add_u64_counter("flush_full", "flushes triggered by "
                                            f"{self.OPT}_max_stripes")
             .add_u64_counter("flush_idle",
                              "flushes triggered by queue idleness")
             .add_time_avg("batch_occupancy",
                           "stripes per flushed batch (long-run avg)")
             .add_time_avg("batch_wait", "seconds an op waited for its "
                                         "flush (long-run avg)")
             .add_u64_counter("flush_failures",
                              f"batched flushes whose device {v} raised "
                              "(the batch disaggregated per-op)")
             .add_u64_counter("per_op_retries",
                              "bounded per-op device retries after a "
                              "failed batch (osd_ec_fallback_retries)")
             .add_u64_counter("fallback_ops",
                              "ops served by the bit-exact reference "
                              f"(numpy) {v}r after device retries "
                              "exhausted"))
        for name, desc in self.EXTRA_COUNTERS:
            b.add_u64_counter(name, desc)
        return b.create_perf_counters(register=False)

    # -- knobs (read LIVE) -------------------------------------------------
    def enabled(self) -> bool:
        return bool(self.config.get(self.OPT, True))

    def window_s(self) -> float:
        return float(self.config.get(f"{self.OPT}_window_us", 500)) / 1e6

    def max_stripes(self) -> int:
        return int(self.config.get(f"{self.OPT}_max_stripes", 4096))

    def _backoff_s(self, failures: int) -> float:
        """Quarantine rest after the ``failures``-th consecutive
        failure: ``base * 2^(failures-1)``, capped."""
        base = float(self.config.get(
            "osd_ec_fallback_quarantine_base", 1.0))
        cap = float(self.config.get(
            "osd_ec_fallback_quarantine_max", 30.0))
        return min(base * (2 ** (failures - 1)), cap)

    # -- submit ------------------------------------------------------------
    async def _submit(self, head: tuple, key: tuple, data, ask, span):
        if not self.enabled() or self.stopped:
            # the measured per-op baseline: one UNPADDED launch per
            # op, exactly the pre-aggregator path — padding here
            # would make the baseline systematically slower than what
            # production previously ran and flatter the aggregator's
            # speedup
            self.perf.inc("bypass")
            try:
                return self._launch(head, data, ask, pad=False, ctx=span)
            except Exception as e:
                return self._degrade_one(head, data, ask, e)
        g = self._groups.get(key)
        if g is None:
            g = self._groups[key] = _Group(head)
        loop = asyncio.get_event_loop()
        fut = loop.create_future()
        g.entries.append(_Entry(
            data, ask, fut, loop.time(),
            span.child("ec.agg_wait") if span is not None else None))
        g.stripes += data.shape[0]
        if g.stripes >= self.max_stripes():
            self._flush(key, g, "full")
        elif g.task is None:
            g.task = asyncio.ensure_future(self._flush_later(key, g))
        return await fut

    async def _flush_later(self, key: tuple, g: _Group) -> None:
        """Window/idle flusher for one group generation. Yields to the
        loop once so a concurrent burst of submitters lands, then
        soaks window slices; two consecutive looks with no new arrival
        mean the queue is idle — flush early instead of pinning a lone
        op to the full window."""
        loop = asyncio.get_event_loop()
        window = self.window_s()
        deadline = loop.time() + window
        seen = -1
        try:
            while True:
                await asyncio.sleep(0)
                if self._groups.get(key) is not g:
                    return                   # full-trigger beat us
                now = loop.time()
                if now >= deadline:
                    self._flush(key, g, "window")
                    return
                if len(g.entries) == seen:
                    self._flush(key, g, "idle")
                    return
                seen = len(g.entries)
                await asyncio.sleep(
                    min(deadline - now, max(window / 8, 1e-4)))
        except asyncio.CancelledError:
            if self._groups.get(key) is g:
                self._flush(key, g, "window")
            raise

    # -- flush -------------------------------------------------------------
    def _flush(self, key: tuple, g: _Group, trigger: str) -> None:
        if self._groups.get(key) is g:
            del self._groups[key]
        if g.task is not None and g.task is not asyncio.current_task():
            g.task.cancel()
            g.task = None
        entries = g.entries
        if not entries:
            return
        # the launch serves every op of the batch; its sections hang
        # off the first one's wait
        ctx = entries[0].span
        with tracing.section("ec.pack", ctx, self.tracer) as sec:
            datas = [e.data for e in entries]
            big = datas[0] if len(datas) == 1 else \
                np.concatenate(datas, axis=0)
            sec.tag("ops", len(entries)).tag("stripes",
                                             int(big.shape[0]))
        try:
            # a batch asks for whatever any of its members asked
            out = self._launch(g.head, big,
                               any(e.ask for e in entries), ctx=ctx)
        except Exception as e:
            self._degrade(g.head, entries, e)
            self._end_waits(entries, trigger)
            return
        off = 0
        now = asyncio.get_event_loop().time()
        for ent in entries:
            b = ent.data.shape[0]
            if not ent.fut.done():
                ent.fut.set_result(self._cut(out, off, off + b, ent.ask))
            self.perf.avg_add("batch_wait", now - ent.t0)
            off += b
        self._end_waits(entries, trigger)
        self.perf.inc("batches")
        self.perf.inc("stripes", int(big.shape[0]))
        self.perf.inc("ops", len(entries))
        self.perf.inc(f"flush_{trigger}")
        self.perf.avg_add("batch_occupancy", float(big.shape[0]))
        log.dout(10, f"{self.name} flush {trigger}: {len(entries)} ops, "
                     f"{big.shape[0]} stripes")

    @staticmethod
    def _end_waits(entries, trigger: str) -> None:
        for ent in entries:
            if ent.span is not None:
                ent.span.tag("trigger", trigger).finish()

    # -- degrade ladder (round 16) -----------------------------------------
    def _degrade(self, head: tuple, entries, err: Exception) -> None:
        """Failed batch flush: DISAGGREGATE — retry each member as its
        own unpadded device launch, then the bit-exact reference; only
        the op whose rows still fail under the reference sees the
        exception. One poisoned stripe must not fail its batchmates,
        and a client write or a degraded READ must never error because
        the accelerator did — the data is reconstructible on the host
        by definition."""
        self.perf.inc("flush_failures")
        log.dout(0, f"{self.name} batch flush failed "
                    f"({type(err).__name__}: {str(err)[:200]}) — "
                    f"disaggregating {len(entries)} ops")
        loop = asyncio.get_event_loop()
        for ent in entries:
            try:
                try:
                    res = self._launch(head, ent.data, ent.ask, pad=False)
                except Exception as e:
                    res = self._degrade_one(head, ent.data, ent.ask, e)
            except Exception as e2:
                if not ent.fut.done():
                    ent.fut.set_exception(e2)
            else:
                if not ent.fut.done():
                    ent.fut.set_result(res)
            self.perf.avg_add("batch_wait", loop.time() - ent.t0)

    def _degrade_one(self, head: tuple, data, ask, err: Exception):
        """Per-op tail of the ladder: osd_ec_fallback_retries more
        device attempts (skipped while the direction's device
        quarantine rests), then the reference (host numpy, bit-exact by
        construction). Raises the last device error only when the
        reference itself fails."""
        exc = err
        if not self._device_resting():
            for _ in range(max(0, int(self.config.get(
                    "osd_ec_fallback_retries", 1)))):
                self.perf.inc("per_op_retries")
                try:
                    return self._launch(head, data, ask, pad=False)
                except Exception as e:
                    exc = e
            self._device_failed(exc)
        try:
            res = self._reference(head, data)
        except Exception:
            raise exc
        self.perf.inc("fallback_ops")
        log.dout(1, f"{self.name} op served by the reference "
                    f"{self.VERB}r ({data.shape[0]} stripes) after "
                    f"device retries exhausted")
        return res

    def _device_resting(self) -> bool:
        """True while a quarantine keeps per-op retries off the device."""
        return False

    def _device_failed(self, err: Exception) -> None:
        """An op's device retries exhausted."""

    # -- the launch's shared stretches -------------------------------------
    @staticmethod
    def _pad(b: int) -> int:
        """Next power of two: bounds the jit cache to O(log) shapes."""
        return 1 << (int(b) - 1).bit_length() if b > 1 else 1

    def _padded(self, data, pad: bool, ctx):
        """``data`` zero-padded to ``_pad`` rows where ``pad`` asks."""
        b = data.shape[0]
        padded = self._pad(b) if pad else b
        if padded != b:
            with tracing.section("ec.pack", ctx, self.tracer) as sec:
                z = np.zeros((padded - b,) + data.shape[1:],
                             dtype=np.uint8)
                data = np.concatenate([data, z], axis=0)
                sec.tag("padded", padded - b)
        return data

    def _device(self, engine: str, call, data, b: int, ctx) -> tuple:
        """``call(data)`` (a tuple of device arrays or None) between its
        two sections. ec.launch: H2D and the enqueue; ec.device_wait:
        the blocking read-back (the device finishes, then D2H) of the
        first ``b`` rows of each."""
        with tracing.section("ec.launch", ctx, self.tracer) as sec:
            sec.tag("engine", engine).tag("stripes", int(data.shape[0]))
            outs = call(data)
        with tracing.section("ec.device_wait", ctx, self.tracer) as sec:
            outs = tuple(None if o is None else np.asarray(o)[:b]
                         for o in outs)
            sec.tag("bytes", int(outs[0].nbytes))
        return outs

    # -- lifecycle / observability ----------------------------------------
    def drain(self) -> int:
        """Daemon stop: flush nothing more — cancel every waiter (their
        PG op workers are being cancelled too) and kill flush timers.
        Returns the number of ops dropped."""
        self.stopped = True
        n = 0
        for g in self._groups.values():
            if g.task is not None:
                g.task.cancel()
                g.task = None
            for ent in g.entries:
                n += 1
                if not ent.fut.done():
                    ent.fut.cancel()
        self._groups.clear()
        return n

    def dump(self) -> dict:
        d = self.perf.dump()

        def avg(name):
            a = d.get(name, {})
            return a.get("sum", 0.0) / a["avgcount"] \
                if a.get("avgcount") else 0.0
        return {
            "enabled": self.enabled(),
            "window_us": float(
                self.config.get(f"{self.OPT}_window_us", 500)),
            "max_stripes": self.max_stripes(),
            "pending_groups": len(self._groups),
            "pending_ops": sum(len(g.entries)
                               for g in self._groups.values()),
            **{c: d.get(c, 0) for c in
               ("batches", "stripes", "ops", "bypass") + self.DUMP_EXTRA},
            "flushes": {t: d.get(f"flush_{t}", 0)
                        for t in ("window", "full", "idle")},
            "avg_occupancy": avg("batch_occupancy"),
            "avg_batch_wait_s": avg("batch_wait"),
        }


class ECAggregator(_WindowedBatcher):
    """The encode direction. One per OSD daemon; every ECPG encode
    routes through it. Groups are keyed by (profile, k, C): two PGs of
    the same pool coalesce even though each holds its own plugin
    instance (the kernel is a pure function of the profile)."""

    OPT = "osd_ec_agg"
    VERB = "encode"
    EXTRA_COUNTERS = (
        ("crc_fallbacks",
         "fused checksum+encode failures that dropped to plain encode "
         "+ host crc (the fused jit quarantines on backoff)"),)

    def __init__(self, config: dict | None = None, tracer=None):
        super().__init__(config, tracer)
        # fused checksum+encode quarantine (round 16): after the fused
        # jit raises, flushes serve plain encode + host crc until the
        # backoff deadline passes, then the fused path is retried
        self._crc_q_until = 0.0
        self._crc_failures = 0

    async def encode(self, ec, data, with_crc: bool = False,
                     span=None):
        """Encode a (B, k, C) uint8 stripe batch; returns
        ``(parity np(B, m, C), row_crcs np(B, k+m) | None)``.
        ``row_crcs`` is None when ``with_crc`` is False or the plugin
        has no fused path (callers fall back to zlib via
        ec.crc.hcrc_attr); the fused checksum applies on the bypass
        too — the fusion is orthogonal to coalescing. ``span``: the
        op's span, where it has one: its ``ec.agg_wait`` child runs
        from here to the op's result (what ``batch_wait`` sums)."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        key = (str(ec.profile), int(data.shape[1]), int(data.shape[2]))
        return await self._submit((ec,), key, data, with_crc, span)

    def _launch(self, head, data, ask, pad=True, ctx=None):
        return self._run(*head, data, ask, pad=pad, ctx=ctx)

    def _cut(self, out, lo, hi, ask):
        parity, crcs = out
        return parity[lo:hi], \
            crcs[lo:hi] if crcs is not None and ask else None

    def _reference(self, head, data):
        """crcs fall back to the caller's zlib path."""
        return np.asarray(head[0].encode_batch_reference(data),
                          dtype=np.uint8), None

    def _run(self, ec, data, want_crc: bool, pad: bool = True,
             ctx=None):
        """One device launch over a (possibly padded) batch. The fused
        checksum+encode jit carries its own quarantine: after it
        raises, flushes drop to plain encode + host crc (callers'
        zlib path) until an exponential-backoff deadline
        (osd_ec_fallback_quarantine_base/_max) passes, then the fused
        path is probed again by simply serving the next crc flush.
        ``ctx``: the span the ``ec.*`` sections hang off."""
        b = data.shape[0]
        data = self._padded(data, pad, ctx)
        if want_crc and time.monotonic() >= self._crc_q_until:
            try:
                out = self._device("encode_crc", ec.encode_batch_with_crc,
                                   data, b, ctx)
            except Exception as e:
                self.perf.inc("crc_fallbacks")
                self._crc_failures += 1
                rest = self._backoff_s(self._crc_failures)
                self._crc_q_until = time.monotonic() + rest
                log.dout(0, f"fused checksum+encode failed "
                            f"({type(e).__name__}: {str(e)[:200]}) — "
                            f"plain encode + host crc for {rest:.2f}s")
            else:
                self._crc_failures = 0
                return out
        return self._device("encode",
                            lambda d: (ec.encode_batch(d), None),
                            data, b, ctx)


class ECReadAggregator(_WindowedBatcher):
    """The decode/repair direction. One per OSD daemon; every ECPG
    decode routes through it. Groups are keyed by (profile, avail,
    want, C): the decode kernel is a pure function of the erasure
    pattern, so only ops reconstructing the same missing set from the
    same available set share a launch — exactly the granularity of
    ``ErasureCodeJax._decode_kernel``'s cache."""

    OPT = "osd_ec_read_agg"
    VERB = "decode"
    EXTRA_COUNTERS = (
        ("quarantined_ops",
         "ops served by the reference decoder while the device decode "
         "sat in failure-backoff quarantine"),
        ("qos_grants",
         "repair decodes that paid a recovery-class size-scaled QoS "
         "grant before queueing"))
    DUMP_EXTRA = ("fallback_ops", "quarantined_ops", "qos_grants")

    def __init__(self, config: dict | None = None, scheduler=None,
                 tracer=None):
        super().__init__(config, tracer)
        self.scheduler = scheduler
        # device-decode quarantine (round 16 hooks): after per-op
        # device retries exhaust, decodes serve the host reference
        # until the backoff deadline passes, then the device is probed
        # again by simply running the next flush on it
        self._dev_q_until = 0.0
        self._dev_failures = 0

    async def decode(self, ec, want, avail, chunks,
                     charge_bytes: int = 0, span=None):
        """Decode a (B, len(avail), C) uint8 batch into the ``want``
        chunk rows; returns np (B, len(want), C).

        ``charge_bytes`` > 0 marks a REPAIR decode (rebuild/backfill):
        a recovery-class QoS grant scaled by
        bytes/osd_qos_cost_per_io_bytes is paid before the op queues,
        the same divisor client writes pay at admission. Client
        degraded reads pass 0 — their cost tag was already charged by
        the daemon's admission path. ``span``: as for ``encode``."""
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        want, avail = tuple(want), tuple(avail)
        if charge_bytes > 0 and self.scheduler is not None \
                and not self.stopped:
            from ceph_tpu.osd.scheduler import size_scaled_cost
            await self.scheduler.grant(
                "recovery",
                cost=size_scaled_cost(self.config, charge_bytes))
            self.perf.inc("qos_grants")
        key = (str(ec.profile), avail, want, int(chunks.shape[2]))
        return await self._submit((ec, want, avail), key, chunks,
                                  False, span)

    def _launch(self, head, data, ask, pad=True, ctx=None):
        return self._run(*head, data, pad=pad, ctx=ctx)

    def _cut(self, out, lo, hi, ask):
        return out[lo:hi]

    def _reference(self, head, data):
        ec, want, avail = head
        return np.asarray(ec.decode_batch_reference(want, avail, data),
                          dtype=np.uint8)

    def _device_resting(self) -> bool:
        return time.monotonic() < self._dev_q_until

    def _device_failed(self, err: Exception) -> None:
        self._dev_failures += 1
        rest = self._backoff_s(self._dev_failures)
        self._dev_q_until = time.monotonic() + rest
        log.dout(0, f"device decode failed "
                    f"({type(err).__name__}: {str(err)[:200]}) — "
                    f"serving the reference decoder for {rest:.2f}s")

    def _run(self, ec, want, avail, chunks, pad: bool = True,
             ctx=None):
        """One device launch over a (possibly padded) batch; while the
        device decode is quarantined, serves the reference decoder
        instead (bit-exact, so callers can't tell beyond latency).
        ``ctx``: the span the ``ec.*`` sections hang off."""
        if self._device_resting():
            self.perf.inc("quarantined_ops")
            return self._reference((ec, want, avail), chunks)
        b = chunks.shape[0]
        out, = self._device(
            "decode", lambda d: (ec.decode_batch(want, avail, d),),
            self._padded(chunks, pad, ctx), b, ctx)
        self._dev_failures = 0
        return out

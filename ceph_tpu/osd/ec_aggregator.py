"""OSD-side EC encode aggregator: cross-op stripe-batch coalescing.

The encode kernel hits its resident rate only on deep batches, but
every client op used to launch its own ``encode_batch`` from
``ECPG._submit_ec_write`` / ``_rebuild_shard`` / the backfill-push
builder — at production traffic (thousands of concurrent small-to-
medium writes) the data path is dispatch-bound, not compute-bound.
This aggregator coalesces concurrent stripe encodes from ALL the PGs
on one OSD into a single padded batched kernel launch per flush
window, amortizing dispatch exactly like the CRUSH sharded sweep
amortizes mapping (PR 10).

Contract:

- **bit-exact**: every encode kernel is stripe-row-independent, so the
  concatenated batch's rows equal the per-op results lane for lane
  (pinned in tests/test_ec_agg.py); the per-op path survives as the
  measured baseline behind ``osd_ec_agg=off`` (read LIVE);
- **latency-bounded**: a batch flushes when ``osd_ec_agg_window_us``
  expires, when ``osd_ec_agg_max_stripes`` accumulate, or when the
  queue goes IDLE (one event-loop yield plus a window slice with no
  new arrivals) — a lone op is never held past the window;
- **fused checksum**: when any waiter wants write-time ``_hcrc``
  stamps, the flush runs the plugin's fused checksum+encode program
  (ec/jax_plugin.encode_batch_with_crc) so checksum+encode stays ONE
  device launch for the whole coalesced batch;
- **padded launches**: the aggregate batch is zero-padded to the next
  power of two before dispatch, so the jit cache sees O(log max_batch)
  distinct shapes instead of one program per concurrency level.

Groups are keyed by (profile, k, C): two PGs of the same pool coalesce
even though each holds its own plugin instance (the kernel is a pure
function of the profile).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from ceph_tpu.utils import tracing
from ceph_tpu.utils.logging import get_logger
from ceph_tpu.utils.perf_counters import PerfCountersBuilder

log = get_logger("osd")


def _agg_perf():
    """Per-OSD counter family (register=False: several in-process OSDs
    each own one; they reach prometheus through the PR 12 daemon->mgr
    report path as ``ceph_osd_ec_agg_*`` rows, not the process-local
    singleton collection)."""
    return (
        PerfCountersBuilder("osd_ec_agg")
        .add_u64_counter("batches", "coalesced kernel launches")
        .add_u64_counter("stripes", "stripes encoded through batches")
        .add_u64_counter("ops", "encode requests served")
        .add_u64_counter("bypass",
                         "encodes served per-op (osd_ec_agg=off)")
        .add_u64_counter("flush_window",
                         "flushes triggered by the window expiring")
        .add_u64_counter("flush_full",
                         "flushes triggered by osd_ec_agg_max_stripes")
        .add_u64_counter("flush_idle",
                         "flushes triggered by queue idleness")
        .add_time_avg("batch_occupancy",
                      "stripes per flushed batch (long-run avg)")
        .add_time_avg("batch_wait",
                      "seconds an op waited for its flush (long-run "
                      "avg)")
        .add_u64_counter("flush_failures",
                         "batched flushes whose device encode raised "
                         "(the batch disaggregated per-op)")
        .add_u64_counter("per_op_retries",
                         "bounded per-op device retries after a "
                         "failed batch (osd_ec_fallback_retries)")
        .add_u64_counter("fallback_ops",
                         "ops served by the bit-exact reference "
                         "(numpy) encoder after device retries "
                         "exhausted")
        .add_u64_counter("crc_fallbacks",
                         "fused checksum+encode failures that dropped "
                         "to plain encode + host crc (the fused jit "
                         "quarantines on backoff)")
        .create_perf_counters(register=False))


class _Entry:
    __slots__ = ("data", "with_crc", "fut", "t0", "span")

    def __init__(self, data, with_crc, fut, t0, span=None):
        self.data = data
        self.with_crc = with_crc
        self.fut = fut
        self.t0 = t0
        self.span = span        # the op's ec.agg_wait interval


class _Group:
    """One in-flight coalescing batch; staleness is decided by
    identity (``self._groups.get(key) is g``), never by counters."""

    __slots__ = ("ec", "entries", "stripes", "task")

    def __init__(self, ec):
        self.ec = ec
        self.entries: list[_Entry] = []
        self.stripes = 0
        self.task: asyncio.Task | None = None


class ECAggregator:
    """One per OSD daemon; every ECPG encode routes through it."""

    def __init__(self, config: dict | None = None, tracer=None):
        self.config = config if config is not None else {}
        self.tracer = tracer    # the owning daemon's, for the sections
        self.perf = _agg_perf()
        self._groups: dict[tuple, _Group] = {}
        self.stopped = False
        # fused checksum+encode quarantine (round 16): after the fused
        # jit raises, flushes serve plain encode + host crc until the
        # backoff deadline passes, then the fused path is retried
        self._crc_q_until = 0.0
        self._crc_failures = 0

    # -- knobs (read LIVE) -------------------------------------------------
    def enabled(self) -> bool:
        return bool(self.config.get("osd_ec_agg", True))

    def window_s(self) -> float:
        return float(self.config.get("osd_ec_agg_window_us", 500)) / 1e6

    def max_stripes(self) -> int:
        return int(self.config.get("osd_ec_agg_max_stripes", 4096))

    def _retries(self) -> int:
        return int(self.config.get("osd_ec_fallback_retries", 1))

    # -- submit ------------------------------------------------------------
    async def encode(self, ec, data, with_crc: bool = False,
                     span=None):
        """Encode a (B, k, C) uint8 stripe batch; returns
        ``(parity np(B, m, C), row_crcs np(B, k+m) | None)``.
        ``row_crcs`` is None when ``with_crc`` is False or the plugin
        has no fused path (callers fall back to zlib via
        ec.crc.hcrc_attr). ``span``: the op's span, where it has one:
        its ``ec.agg_wait`` child runs from here to the op's result
        (what ``batch_wait`` sums)."""
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if not self.enabled() or self.stopped:
            # the measured per-op baseline: one UNPADDED launch per
            # op, exactly the pre-aggregator path — padding here
            # would make the baseline systematically slower than what
            # production previously ran and flatter the aggregator's
            # speedup (fused checksum still applies — the fusion is
            # orthogonal to coalescing)
            self.perf.inc("bypass")
            try:
                return self._run(ec, data, with_crc, pad=False, ctx=span)
            except Exception as e:
                return self._degrade_one(ec, data, with_crc, e)
        key = (str(ec.profile), int(data.shape[1]), int(data.shape[2]))
        g = self._groups.get(key)
        if g is None:
            g = self._groups[key] = _Group(ec)
        loop = asyncio.get_event_loop()
        fut = loop.create_future()
        g.entries.append(_Entry(
            data, with_crc, fut, loop.time(),
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
        want_crc = any(e.with_crc for e in entries)
        loop = asyncio.get_event_loop()
        try:
            parity, crcs = self._run(g.ec, big, want_crc, ctx=ctx)
        except Exception as e:
            self._degrade(g.ec, entries, e)
            self._end_waits(entries, trigger)
            return
        off = 0
        now = loop.time()
        for ent in entries:
            b = ent.data.shape[0]
            res = (parity[off:off + b],
                   crcs[off:off + b]
                   if crcs is not None and ent.with_crc else None)
            if not ent.fut.done():
                ent.fut.set_result(res)
            self.perf.avg_add("batch_wait", now - ent.t0)
            off += b
        self._end_waits(entries, trigger)
        self.perf.inc("batches")
        self.perf.inc("stripes", int(big.shape[0]))
        self.perf.inc("ops", len(entries))
        self.perf.inc(f"flush_{trigger}")
        self.perf.avg_add("batch_occupancy", float(big.shape[0]))
        log.dout(10, f"ec_agg flush {trigger}: {len(entries)} ops, "
                     f"{big.shape[0]} stripes")

    @staticmethod
    def _end_waits(entries, trigger: str) -> None:
        for ent in entries:
            if ent.span is not None:
                ent.span.tag("trigger", trigger).finish()

    # -- degrade ladder (round 16) -----------------------------------------
    def _degrade(self, ec, entries, err: Exception) -> None:
        """Failed batch flush: DISAGGREGATE — retry each member stripe
        as its own device encode, then the bit-exact reference (numpy)
        encoder; only the op whose stripe still fails under the
        reference sees the exception. One poisoned stripe must not
        fail its batchmates, and a client write must never error
        because the accelerator did."""
        self.perf.inc("flush_failures")
        log.dout(0, f"ec_agg batch flush failed "
                    f"({type(err).__name__}: {str(err)[:200]}) — "
                    f"disaggregating {len(entries)} ops")
        loop = asyncio.get_event_loop()
        for ent in entries:
            try:
                res = self._run(ec, ent.data, ent.with_crc, pad=False)
            except Exception as e:
                try:
                    res = self._degrade_one(ec, ent.data,
                                            ent.with_crc, e)
                except Exception as e2:
                    if not ent.fut.done():
                        ent.fut.set_exception(e2)
                    self.perf.avg_add("batch_wait",
                                      loop.time() - ent.t0)
                    continue
            if not ent.fut.done():
                ent.fut.set_result(res)
            self.perf.avg_add("batch_wait", loop.time() - ent.t0)

    def _degrade_one(self, ec, data, with_crc: bool, err: Exception):
        """Per-op tail of the ladder: osd_ec_fallback_retries more
        device attempts, then the reference encoder (host numpy,
        bit-exact by construction; crcs fall back to the caller's
        zlib path). Raises the last device error only when the
        reference itself fails."""
        exc = err
        for _ in range(max(0, self._retries())):
            self.perf.inc("per_op_retries")
            try:
                return self._run(ec, data, with_crc, pad=False)
            except Exception as e:
                exc = e
        try:
            parity = np.asarray(ec.encode_batch_reference(data),
                                dtype=np.uint8)
        except Exception:
            raise exc
        self.perf.inc("fallback_ops")
        log.dout(1, f"ec_agg op served by the reference encoder "
                    f"({data.shape[0]} stripes) after device retries "
                    f"exhausted")
        return parity, None

    @staticmethod
    def _pad(b: int) -> int:
        """Next power of two: bounds the jit cache to O(log) shapes."""
        return 1 << (int(b) - 1).bit_length() if b > 1 else 1

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
        padded = self._pad(b) if pad else b
        if padded != b:
            with tracing.section("ec.pack", ctx, self.tracer) as sec:
                pad = np.zeros((padded - b,) + data.shape[1:],
                               dtype=np.uint8)
                data = np.concatenate([data, pad], axis=0)
                sec.tag("padded", padded - b)
        if want_crc and time.monotonic() >= self._crc_q_until:
            try:
                # ec.launch: H2D and the enqueue; ec.device_wait: the
                # blocking read-back (the device finishes, then D2H)
                with tracing.section("ec.launch", ctx,
                                     self.tracer) as sec:
                    sec.tag("engine", "encode_crc").tag("stripes",
                                                        padded)
                    parity, crcs = ec.encode_batch_with_crc(data)
                with tracing.section("ec.device_wait", ctx,
                                     self.tracer) as sec:
                    parity = np.asarray(parity)[:b]
                    crcs = None if crcs is None \
                        else np.asarray(crcs)[:b]
                    sec.tag("bytes", int(parity.nbytes))
            except Exception as e:
                self._crc_fail(e)
            else:
                self._crc_failures = 0
                return parity, crcs
        with tracing.section("ec.launch", ctx, self.tracer) as sec:
            sec.tag("engine", "encode").tag("stripes", padded)
            parity = ec.encode_batch(data)
        with tracing.section("ec.device_wait", ctx, self.tracer) as sec:
            parity = np.asarray(parity)[:b]
            sec.tag("bytes", int(parity.nbytes))
        return parity, None

    def _crc_fail(self, e: Exception) -> None:
        self.perf.inc("crc_fallbacks")
        self._crc_failures += 1
        base = float(self.config.get(
            "osd_ec_fallback_quarantine_base", 1.0))
        cap = float(self.config.get(
            "osd_ec_fallback_quarantine_max", 30.0))
        backoff = min(base * (2 ** (self._crc_failures - 1)), cap)
        self._crc_q_until = time.monotonic() + backoff
        log.dout(0, f"fused checksum+encode failed "
                    f"({type(e).__name__}: {str(e)[:200]}) — plain "
                    f"encode + host crc for {backoff:.2f}s")

    # -- lifecycle / observability ----------------------------------------
    def drain(self) -> int:
        """Daemon stop: flush nothing more — cancel every waiter (their
        PG op workers are being cancelled too) and kill flush timers.
        Returns the number of ops dropped."""
        self.stopped = True
        n = 0
        for key, g in list(self._groups.items()):
            if g.task is not None:
                g.task.cancel()
                g.task = None
            for ent in g.entries:
                n += 1
                if not ent.fut.done():
                    ent.fut.cancel()
            self._groups.pop(key, None)
        return n

    def dump(self) -> dict:
        d = self.perf.dump()
        occ = d.get("batch_occupancy", {})
        wait = d.get("batch_wait", {})
        return {
            "enabled": self.enabled(),
            "window_us": float(
                self.config.get("osd_ec_agg_window_us", 500)),
            "max_stripes": self.max_stripes(),
            "pending_groups": len(self._groups),
            "pending_ops": sum(len(g.entries)
                               for g in self._groups.values()),
            "batches": d.get("batches", 0),
            "stripes": d.get("stripes", 0),
            "ops": d.get("ops", 0),
            "bypass": d.get("bypass", 0),
            "flushes": {t: d.get(f"flush_{t}", 0)
                        for t in ("window", "full", "idle")},
            "avg_occupancy": (occ.get("sum", 0.0) /
                              occ.get("avgcount", 1)
                              if occ.get("avgcount") else 0.0),
            "avg_batch_wait_s": (wait.get("sum", 0.0) /
                                 wait.get("avgcount", 1)
                                 if wait.get("avgcount") else 0.0),
        }

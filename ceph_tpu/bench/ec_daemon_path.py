"""The ``ec_daemon_path`` bench section: the READ-side data path.

Round 19's tentpole moved the OSD's decode/repair traffic behind
``osd/ec_aggregator.ECReadAggregator`` — the decode direction of the
OSD's windowed batcher. This section measures the same op mix
(n_ops concurrent "degraded reads", each a (stripes_per_op, k, C)
survivor-chunk batch decoding one lost data chunk) through three legs:

- ``per_op_GiBs`` — the ``osd_ec_read_agg=off`` baseline: one decode
  launch + readback per op, exactly what every degraded ``_gather``
  used to pay (dispatch-bound at production op sizes);
- ``read_agg_GiBs`` — the ops submitted CONCURRENTLY through the real
  aggregator, coalescing into padded batched decode launches (the
  tentpole path);
- ``resident_GiBs`` — survivor chunks already on device, the decode
  kernel's own rate with the same readback anchoring (the ceiling the
  daemon path is judged against).

Verdict (driver-parsed compact tail): ``daemon_within_2x_resident`` —
the aggregated daemon-path rate lands within 2x of the resident rate.
All rates account survivor input bytes (k * C per stripe), matching
the ``ec_streaming`` accounting. TPU runs the production shape; CPU
boxes run a smoke size with the SAME schema — on CPU the decode kernel
is host-speed so the per-op/aggregated legs are asyncio-dispatch-bound
and the verdict documents scheduling overhead, not MXU rates (the
``cpu_caveat`` field says so in the record).
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np

import jax

from ceph_tpu.bench import device_stamp
from ceph_tpu.ec.jax_plugin import ErasureCodeJax
from ceph_tpu.osd.ec_aggregator import ECReadAggregator


def _default_shape() -> tuple[int, int, int]:
    """(n_ops, stripes_per_op, chunk_size): production shape on TPU,
    smoke on CPU (env overrides win)."""
    if jax.devices()[0].platform == "tpu":
        shape = (256, 32, 4096)      # 256 degraded reads x 1 MiB each
    else:
        shape = (16, 4, 1024)
    return (
        int(os.environ.get("CEPH_TPU_BENCH_ECDAEMON_OPS", shape[0])),
        int(os.environ.get("CEPH_TPU_BENCH_ECDAEMON_STRIPES",
                           shape[1])),
        int(os.environ.get("CEPH_TPU_BENCH_ECDAEMON_CHUNK", shape[2])),
    )


def _rate(nbytes: int, seconds: float) -> float:
    return nbytes / max(seconds, 1e-9) / (1 << 30)


def ec_daemon_path_section(n_ops: int | None = None,
                           stripes_per_op: int | None = None,
                           chunk_size: int | None = None,
                           k: int = 8, m: int = 3,
                           reps: int = 3) -> dict:
    """Run the section; every knob defaulting per platform. The
    returned record is JSON-clean and carries the driver-required
    keys: ``per_op_GiBs``, ``read_agg_GiBs``, ``resident_GiBs``,
    ``daemon_within_2x_resident``."""
    d_ops, d_stripes, d_chunk = _default_shape()
    n_ops = n_ops or d_ops
    stripes_per_op = stripes_per_op or d_stripes
    chunk_size = chunk_size or d_chunk
    ec = ErasureCodeJax(f"plugin=jax k={k} m={m} "
                        f"technique=reed_sol_van")
    rng = np.random.default_rng(19)
    # each op: k survivor chunks (data chunk 0 lost, chunks 1..k held)
    want = [0]
    avail = list(range(1, k + 1))
    ops = [rng.integers(0, 256, (stripes_per_op, k, chunk_size),
                        dtype=np.uint8) for _ in range(n_ops)]
    op_bytes = stripes_per_op * k * chunk_size
    total_bytes = n_ops * op_bytes

    np.asarray(ec.decode_batch(want, avail, ops[0]))    # warm/compile

    # -- per-op baseline (osd_ec_read_agg=off): launch per op ----------
    agg_off = ECReadAggregator({"osd_ec_read_agg": False})

    async def _per_op() -> float:
        t0 = time.perf_counter()
        for d in ops:
            await agg_off.decode(ec, want, avail, d)
        return time.perf_counter() - t0

    per_op_s = min(asyncio.run(_per_op()) for _ in range(reps))

    # -- aggregated: concurrent ops through the real aggregator --------
    async def _aggregated() -> tuple[float, int]:
        agg = ECReadAggregator({
            "osd_ec_read_agg": True,
            "osd_ec_read_agg_window_us": 2000.0,
            "osd_ec_read_agg_max_stripes":
                max(n_ops * stripes_per_op, 1)})
        # warm BOTH shapes the timed region can launch outside it:
        # the coalesced full batch's padded shape and a lone op's
        # (an idle flush racing the gather can emit a partial batch)
        agg._run(ec, want, avail, np.concatenate(ops, axis=0))
        await agg.decode(ec, want, avail, ops[0])
        warm_batches = agg.perf.dump()["batches"]
        t0 = time.perf_counter()
        await asyncio.gather(*[agg.decode(ec, want, avail, d)
                               for d in ops])
        dt = time.perf_counter() - t0
        return dt, agg.perf.dump()["batches"] - warm_batches

    # keep the batch count FROM the min-time rep: reporting rep 1's
    # rate beside rep 3's launch count would misdescribe the run
    agg_s, agg_batches = min(
        (asyncio.run(_aggregated()) for _ in range(reps)),
        key=lambda r: r[0])

    # -- resident reference: survivor chunks already on device ---------
    dev = jax.device_put(np.concatenate(ops, axis=0))
    np.asarray(ec.decode_batch(want, avail, dev))       # warm

    def _resident_once() -> float:
        t0 = time.perf_counter()
        out = ec.decode_batch(want, avail, dev)
        np.asarray(out)                  # readback anchor
        return time.perf_counter() - t0

    resident = _rate(total_bytes,
                     min(_resident_once() for _ in range(reps)))

    aggregated = _rate(total_bytes, agg_s)
    platform = jax.devices()[0].platform
    rec = {
        "n_ops": n_ops,
        "stripes_per_op": stripes_per_op,
        "chunk_size": chunk_size,
        "k": k, "m": m,
        "op_bytes": op_bytes,
        "total_bytes": total_bytes,
        "backend": ec.backend,
        **device_stamp(),
        "per_op_GiBs": round(_rate(total_bytes, per_op_s), 4),
        "read_agg_GiBs": round(aggregated, 4),
        "resident_GiBs": round(resident, 4),
        "read_agg_batches": int(agg_batches),
        "read_agg_speedup_vs_per_op": round(
            per_op_s / max(agg_s, 1e-9), 2),
        "daemon_within_2x_resident": bool(
            aggregated * 2.0 >= resident),
    }
    if platform != "tpu":
        rec["cpu_caveat"] = (
            "CPU smoke leg: decode is host-speed, so per-op and "
            "aggregated rates are asyncio-dispatch-bound — the "
            "verdict documents scheduling overhead here, not the "
            "TPU kernel ratio")
    return rec

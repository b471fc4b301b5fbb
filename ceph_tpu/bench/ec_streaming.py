"""The ``ec_streaming`` bench section: EC data path at production traffic.

Three measured legs over the SAME op mix (n_ops concurrent "client
ops", each a (stripes_per_op, k, C) stripe batch), plus the resident
reference:

- ``per_op_GiBs`` — the ``osd_ec_agg=off`` baseline: one kernel launch
  + readback per op, exactly what every ``_submit_ec_write`` used to
  pay (dispatch-bound at production op sizes);
- ``aggregated_GiBs`` — the ops submitted CONCURRENTLY through the
  real ``osd/ec_aggregator.ECAggregator``, coalescing into padded
  batched launches (the tentpole path);
- ``pipeline_GiBs`` — the double-buffered H2D/D2H streaming pipeline
  (``ec/jax_plugin.StreamingEncodePipeline``): host batches in, parity
  out, transfer of batch N+1 overlapped with encode of batch N — the
  honest host-transfer-bound rate (PCIe on a real host) instead of
  the dispatch-serialized streamed row;
- ``resident_GiBs`` — data already on device, the kernel's own rate
  (the BENCH headline methodology at this section's shape), measured
  with the same readback anchoring.

Verdict (driver-parsed compact tail): ``ec_agg_within_2x`` — the
aggregated multi-op rate lands within 2x of the resident rate. All
rates account input bytes (k * C per stripe), matching the headline
encode accounting. TPU runs the production shape; CPU boxes run a
smoke size with the SAME schema (SURVEY §7 discipline).
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np

import jax

from ceph_tpu.bench import device_stamp
from ceph_tpu.ec.jax_plugin import ErasureCodeJax, StreamingEncodePipeline
from ceph_tpu.osd.ec_aggregator import ECAggregator


def _default_shape() -> tuple[int, int, int]:
    """(n_ops, stripes_per_op, chunk_size): production shape on TPU,
    smoke on CPU (env overrides win)."""
    if jax.devices()[0].platform == "tpu":
        shape = (256, 32, 4096)      # 256 ops x 1 MiB input each
    else:
        shape = (16, 4, 1024)
    return (
        int(os.environ.get("CEPH_TPU_BENCH_ECSTREAM_OPS", shape[0])),
        int(os.environ.get("CEPH_TPU_BENCH_ECSTREAM_STRIPES",
                           shape[1])),
        int(os.environ.get("CEPH_TPU_BENCH_ECSTREAM_CHUNK", shape[2])),
    )


def _rate(nbytes: int, seconds: float) -> float:
    return nbytes / max(seconds, 1e-9) / (1 << 30)


def ec_streaming_section(n_ops: int | None = None,
                         stripes_per_op: int | None = None,
                         chunk_size: int | None = None,
                         k: int = 8, m: int = 3,
                         resident_gibs: float | None = None,
                         reps: int = 3) -> dict:
    """Run the section; every knob defaulting per platform. The
    returned record is JSON-clean and carries the driver-required
    keys: ``aggregated_GiBs``, ``per_op_GiBs``, ``pipeline_GiBs``,
    ``resident_GiBs``, ``ec_agg_within_2x``."""
    d_ops, d_stripes, d_chunk = _default_shape()
    n_ops = n_ops or d_ops
    stripes_per_op = stripes_per_op or d_stripes
    chunk_size = chunk_size or d_chunk
    ec = ErasureCodeJax(f"plugin=jax k={k} m={m} "
                        f"technique=reed_sol_van")
    rng = np.random.default_rng(13)
    ops = [rng.integers(0, 256, (stripes_per_op, k, chunk_size),
                        dtype=np.uint8) for _ in range(n_ops)]
    op_bytes = stripes_per_op * k * chunk_size
    total_bytes = n_ops * op_bytes

    def _warm(data):
        np.asarray(ec.encode_batch(data))

    _warm(ops[0])

    # -- per-op baseline (osd_ec_agg=off): launch+readback per op ------
    agg_off = ECAggregator({"osd_ec_agg": False})

    async def _per_op() -> float:
        t0 = time.perf_counter()
        for d in ops:
            await agg_off.encode(ec, d)
        return time.perf_counter() - t0

    per_op_s = min(asyncio.run(_per_op()) for _ in range(reps))

    # -- aggregated: concurrent ops through the real aggregator --------
    async def _aggregated() -> tuple[float, int]:
        agg = ECAggregator({"osd_ec_agg": True,
                            "osd_ec_agg_window_us": 2000.0,
                            "osd_ec_agg_max_stripes":
                                max(n_ops * stripes_per_op, 1)})
        # warm BOTH shapes the timed region can launch outside it:
        # the coalesced full batch's padded shape and a lone op's
        # (an idle flush racing the gather can emit a partial batch)
        agg._run(ec, np.concatenate(ops, axis=0), False)
        await agg.encode(ec, ops[0])
        warm_batches = agg.perf.dump()["batches"]
        t0 = time.perf_counter()
        await asyncio.gather(*[agg.encode(ec, d) for d in ops])
        dt = time.perf_counter() - t0
        return dt, agg.perf.dump()["batches"] - warm_batches

    # keep the batch count FROM the min-time rep: reporting rep 1's
    # rate beside rep 3's launch count would misdescribe the run
    agg_s, agg_batches = min(
        (asyncio.run(_aggregated()) for _ in range(reps)),
        key=lambda r: r[0])

    # -- double-buffered streaming pipeline ----------------------------
    # (same min-over-reps noise rejection as the other legs — the
    # within-2x verdict must not compare a best-of rate against
    # single-shot references)
    pipe = StreamingEncodePipeline(ec)
    pipe.encode_all(ops[:2])                 # warm/compile

    def _pipe_once() -> float:
        t0 = time.perf_counter()
        pipe.encode_all(ops)
        return time.perf_counter() - t0

    pipe_s = min(_pipe_once() for _ in range(reps))

    # -- resident reference (or the headline number, when passed) ------
    measured_resident = resident_gibs is None
    if measured_resident:
        dev = jax.device_put(
            np.concatenate(ops, axis=0))     # one deep resident batch
        np.asarray(ec.encode_batch(dev))     # warm

        def _resident_once() -> float:
            t0 = time.perf_counter()
            out = ec.encode_batch(dev)
            np.asarray(out)                  # readback anchor
            return time.perf_counter() - t0

        resident_gibs = _rate(total_bytes,
                              min(_resident_once()
                                  for _ in range(reps)))

    aggregated = _rate(total_bytes, agg_s)
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
        "aggregated_GiBs": round(aggregated, 4),
        "pipeline_GiBs": round(_rate(total_bytes, pipe_s), 4),
        "resident_GiBs": round(float(resident_gibs), 4),
        "resident_measured_here": bool(measured_resident),
        "agg_batches": int(agg_batches),
        "agg_speedup_vs_per_op": round(per_op_s / max(agg_s, 1e-9), 2),
        "ec_agg_within_2x": bool(
            aggregated * 2.0 >= float(resident_gibs)),
    }
    return rec

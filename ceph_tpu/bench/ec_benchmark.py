"""Erasure-code benchmark, flag-compatible with the reference harness.

ref: src/test/erasure-code/ceph_erasure_code_benchmark.{h,cc}
(ErasureCodeBench::setup / run / encode / decode). Same flags:

    python -m ceph_tpu.bench.ec_benchmark \
        --plugin jax --workload encode --size 4194304 --iterations 1024 \
        --parameter k=8 --parameter m=3 --parameter technique=reed_sol_van

Output keeps the reference's two-column ``<seconds> <MB/s>`` line (the
reference prints elapsed seconds and throughput), followed by an optional
JSON record with full detail (--json).

TPU adaptation: the reference encodes one `size` buffer per iteration in a
host loop; here iterations are tiled into on-device stripe batches so the
MXU sees deep batches — same total bytes, same per-op geometry. ``--stream``
additionally measures host->device transfer in the loop (the honest
PCIe-bound number; default keeps data resident like the reference's reuse of
one in-RAM buffer).

Timing methodology (round 2, replacing round 1's invalid dispatch-timed
loop): device paths are measured with the chained readback-anchored slope
method of ``ceph_tpu.utils.timing`` — each step's input depends on the
previous step's full output, the timed program ends in a scalar readback,
and the per-step time is the slope between two step counts so the RPC
dispatch floor cancels. Every reported rate passes the physical-bound guard
in ``ceph_tpu.utils.roofline`` (a number above the device's HBM/MXU roofline
raises instead of printing). Host-loop plugins (lrc/shec/clay base paths)
keep plain wall-clock, which is sound for synchronous numpy.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from ceph_tpu.ec.interface import ErasureCodeInterface, ErasureCodeProfile
from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.utils import roofline, timing
from ceph_tpu.utils.logging import get_logger

log = get_logger("bench")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="ceph_erasure_code_benchmark",
        description="erasure code benchmark (TPU-native)")
    ap.add_argument("-p", "--plugin", default="jax")
    ap.add_argument("-w", "--workload", default="encode",
                    choices=["encode", "decode"])
    ap.add_argument("-s", "--size", type=int, default=1 << 20,
                    help="object bytes per operation")
    ap.add_argument("-i", "--iterations", type=int, default=1)
    ap.add_argument("-P", "--parameter", action="append", default=[],
                    help="profile key=value (repeatable)")
    ap.add_argument("-e", "--erasures", type=int, default=1,
                    help="chunks to erase for decode workload")
    ap.add_argument("--erased", action="append", type=int, default=None,
                    help="explicit chunk ids to erase (repeatable)")
    ap.add_argument("--batch", type=int, default=0,
                    help="stripes per device step (0 = auto)")
    ap.add_argument("--stream", action="store_true",
                    help="include host->device transfer per step")
    ap.add_argument("--json", action="store_true", help="emit JSON detail")
    ap.add_argument("--slope-steps", nargs=2, type=int, default=None,
                    metavar=("LO", "HI"),
                    help="step counts for the chained-slope measurement")
    ap.add_argument("--perf-dump", action="store_true",
                    help="dump perf counters after the run "
                         "(`ceph daemon ... perf dump` analog)")
    ap.add_argument("-v", "--verbose", action="store_true")
    return ap.parse_args(argv)


def _readback(x) -> None:
    """Force execution by reading the result back to host: a D2H copy
    cannot complete before the value exists (see utils/timing.py)."""
    np.asarray(x)


# Working-set multiple of the input bytes each backend materializes in HBM
# (bit-planes at 8x + int32 accumulator rows for bitmatmul; the (m, k, L)
# nibble-product intermediate for lut — measured from XLA OOM dumps).
_HBM_MULTIPLE = {"bitmatmul": 16, "lut": 72, "pallas": 3}


def _auto_batch(object_size: int, iterations: int, backend: str,
                spec: roofline.DeviceSpec | None) -> int:
    """Stripes/step: fill the device without overflowing HBM (round 1
    ignored HBM and OOMed the lut path at 256 MiB input)."""
    target = 256 << 20
    if spec is not None:
        mult = _HBM_MULTIPLE.get(backend, 16)
        target = min(target, int(spec.hbm_bytes * 0.5) // mult)
    return max(1, min(iterations, target // max(object_size, 1)))


class ErasureCodeBench:
    """ref: ErasureCodeBench (same setup/run/encode/decode split)."""

    def __init__(self, args: argparse.Namespace):
        self.args = args
        profile = ErasureCodeProfile.parse(
            " ".join(args.parameter) or "k=2 m=2")
        profile.setdefault("plugin", args.plugin)
        self.profile = profile
        if args.iterations < 1:
            raise SystemExit("--iterations must be >= 1")
        self.ec = ErasureCodePluginRegistry.instance().factory(
            args.plugin, profile)
        self.k = self.ec.k
        self.m = self.ec.m
        self.chunk = self.ec.get_chunk_size(args.size)
        self.spec = roofline.device_spec()
        backend = getattr(self.ec, "backend", "bitmatmul")
        self.batch = args.batch or _auto_batch(
            args.size, args.iterations, backend, self.spec)
        # device path iff the plugin overrides the matching batched kernel
        # (lrc overrides encode_batch but inherits the numpy decode_batch —
        # the two workloads must be classified independently)
        self.device_path = (
            type(self.ec).encode_batch
            is not ErasureCodeInterface.encode_batch
            if args.workload == "encode"
            else type(self.ec).decode_batch
            is not ErasureCodeInterface.decode_batch)
        from ceph_tpu.utils.perf_counters import PerfCountersBuilder
        self.perf = (PerfCountersBuilder("ec_bench")
                     .add_u64_counter("encode_bytes", "input bytes encoded")
                     .add_u64_counter("decode_bytes", "chunk bytes read for decode")
                     .add_u64_counter("encode_ops", "stripe encodes")
                     .add_u64_counter("decode_ops", "stripe decodes")
                     .add_time("encode_seconds", "time in timed encode region")
                     .add_time("decode_seconds", "time in timed decode region")
                     .create_perf_counters())

    # -- workloads --------------------------------------------------------
    def _make_data(self, rng) -> np.ndarray:
        return rng.integers(0, 256, size=(self.batch, self.k, self.chunk),
                            dtype=np.uint8)

    def _slope_steps(self) -> tuple[int, int]:
        if self.args.slope_steps:
            lo, hi = self.args.slope_steps
            return int(lo), int(hi)
        return (2, 10)

    def encode(self) -> dict:
        rng = np.random.default_rng(0)
        host = self._make_data(rng)
        if not self.device_path:
            return self._encode_hostloop(host)
        if self.args.stream:
            return self._encode_stream(host)
        data = jnp.asarray(host)

        def step(carry):
            d, acc = carry
            parity = self.ec.encode_batch(d)
            acc = acc ^ timing.xor_anchor(parity)
            # fold the digest of the FULL parity back into the next input:
            # XLA cannot elide any lane, and steps cannot overlap
            d = jax.lax.dynamic_update_slice(
                d, acc[None, None, None], (0, 0, 0))
            return (d, acc)

        t = timing.measure_chained(step, (data, jnp.uint8(0)),
                                   lambda c: c[1],
                                   steps=self._slope_steps())
        return self._result("encode", t.seconds_per_step, self.batch,
                            timing_detail=t.as_dict(),
                            steps_run=t.steps_executed,
                            region_s=t.timed_region_s)

    def _encode_stream(self, host: np.ndarray) -> dict:
        """Streamed mode: H2D transfer inside the loop, pipelining allowed
        (that is how a real ingest pipeline runs); anchored by a final
        readback — in-order device execution means the last program
        completing implies all did."""
        steps = max(4, -(-self.args.iterations // self.batch))
        out = self.ec.encode_batch(jnp.asarray(host))  # warm/compile
        _readback(timing.xor_anchor(out))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = self.ec.encode_batch(jnp.asarray(host))
        _readback(timing.xor_anchor(out))
        elapsed = time.perf_counter() - t0
        return self._result("encode", elapsed / steps, self.batch,
                            timing_detail={"method":
                                           "streamed_pipeline_readback",
                                           "steps": steps},
                            steps_run=steps, region_s=elapsed)

    def _encode_hostloop(self, host: np.ndarray) -> dict:
        """Host plugins (lrc/shec/clay base paths): synchronous numpy, so
        plain wall-clock is sound."""
        steps = -(-self.args.iterations // self.batch)
        self.ec.encode_batch(host)  # warm any caches
        t0 = time.perf_counter()
        for _ in range(steps):
            out = self.ec.encode_batch(host)
        np.asarray(out)
        elapsed = time.perf_counter() - t0
        return self._result("encode", elapsed / steps, self.batch,
                            timing_detail={"method": "host_wallclock",
                                           "steps": steps},
                            steps_run=steps, region_s=elapsed)

    def _decode_setup(self):
        rng = np.random.default_rng(0)
        host = self._make_data(rng)
        data = jnp.asarray(host)
        parity = self.ec.encode_batch(data)
        full = jnp.concatenate([data, jnp.asarray(parity)], axis=1)
        n = self.ec.get_chunk_count()
        if self.args.erased:
            erased = sorted(set(self.args.erased))
        else:
            erased = list(range(self.args.erasures))
        avail = [i for i in range(n) if i not in erased]
        if self.ec.is_mds():
            avail = avail[:self.k]  # MDS: any k; layered codes keep all
        chunks = full[:, jnp.asarray(avail), :]
        return erased, avail, chunks

    def decode(self) -> dict:
        erased, avail, chunks = self._decode_setup()
        if not self.device_path:
            host_chunks = np.asarray(chunks)
            steps = -(-self.args.iterations // self.batch)
            self.ec.decode_batch(erased, avail, host_chunks)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = self.ec.decode_batch(erased, avail, host_chunks)
            np.asarray(out)
            elapsed = time.perf_counter() - t0
            return self._result(
                "decode", elapsed / steps, self.batch, erased=erased,
                avail=avail,
                timing_detail={"method": "host_wallclock", "steps": steps},
                steps_run=steps, region_s=elapsed)
        if self.args.stream:
            return self._decode_stream(erased, avail, chunks)

        # Build the per-pattern decode kernel eagerly: inside the traced
        # loop a cache miss would stage its constants as tracers.
        self.ec.decode_batch(erased, avail, chunks)

        def step(carry):
            c, acc = carry
            out = self.ec.decode_batch(erased, avail, c)
            acc = acc ^ timing.xor_anchor(out)
            c = jax.lax.dynamic_update_slice(
                c, acc[None, None, None], (0, 0, 0))
            return (c, acc)

        t = timing.measure_chained(step, (chunks, jnp.uint8(0)),
                                   lambda c: c[1],
                                   steps=self._slope_steps())
        return self._result("decode", t.seconds_per_step, self.batch,
                            erased=erased, avail=avail,
                            timing_detail=t.as_dict(),
                            steps_run=t.steps_executed,
                            region_s=t.timed_region_s)

    def _decode_stream(self, erased, avail, chunks) -> dict:
        """Streamed decode: H2D of the survivor chunks inside the loop
        (see _encode_stream for the pipelining/anchoring rationale)."""
        host_chunks = np.asarray(chunks)
        steps = max(4, -(-self.args.iterations // self.batch))
        out = self.ec.decode_batch(erased, avail, jnp.asarray(host_chunks))
        _readback(timing.xor_anchor(out))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = self.ec.decode_batch(erased, avail,
                                       jnp.asarray(host_chunks))
        _readback(timing.xor_anchor(out))
        elapsed = time.perf_counter() - t0
        return self._result("decode", elapsed / steps, self.batch,
                            erased=erased, avail=avail,
                            timing_detail={"method":
                                           "streamed_pipeline_readback",
                                           "steps": steps},
                            steps_run=steps, region_s=elapsed)

    def _result(self, workload: str, seconds_per_step: float,
                ops_per_step: int, erased=None, avail=None,
                timing_detail=None, steps_run: int = 1,
                region_s: float | None = None) -> dict:
        """Throughput accounting (round 2, fixing round 1's Weak #6):

        encode: bytes = input bytes (k * chunk per op) — the reference's
        accounting for ``--workload encode``.
        decode: headline bytes = chunk bytes actually READ
        (len(avail) * chunk per op); ``reconstructed_bytes`` = erased
        chunks produced; ``object_MBps`` = the reference-comparable rate in
        object bytes (k * chunk per op, what ErasureCodeBench::decode
        reports), stated separately so no single number overstates work.
        """
        n_read = len(avail) if avail is not None else self.k
        if workload == "encode":
            step_bytes = ops_per_step * self.k * self.chunk
            bound = (roofline.encode_bound(self.k, self.m, self.spec)
                     if self.spec else None)
        else:
            step_bytes = ops_per_step * n_read * self.chunk
            bound = (roofline.decode_bound(len(erased or []), n_read,
                                           self.spec)
                     if self.spec else None)
        rate = step_bytes / seconds_per_step
        if not self.args.stream:  # streamed mode is PCIe-bound, not device
            roofline.check(rate, bound, f"{workload} throughput")
        # Counters account everything the device actually executed
        # (warmup + all timed reps), not just one step.
        self.perf.inc(f"{workload}_bytes", step_bytes * steps_run)
        self.perf.inc(f"{workload}_ops", ops_per_step * steps_run)
        self.perf.tinc(f"{workload}_seconds",
                       region_s if region_s is not None
                       else seconds_per_step * steps_run)
        res = {
            "workload": workload,
            "plugin": self.args.plugin,
            "technique": self.ec.profile.get("technique", "reed_sol_van"),
            "k": self.k, "m": self.m,
            "object_size": self.args.size,
            "chunk_size": self.chunk,
            "batch": ops_per_step,
            "seconds": seconds_per_step,      # per step of `batch` ops
            "total_bytes": step_bytes,        # accounted bytes per step
            "MB/s": rate / 1e6,
            "GiB/s": rate / (1 << 30),
            "backend": getattr(self.ec, "backend", "n/a"),
            "stream": self.args.stream,
            "platform": jax.devices()[0].platform,
            "device": jax.devices()[0].device_kind,
            "roofline_GiB/s": (bound / (1 << 30)) if bound else None,
            "timing": timing_detail or {},
        }
        if workload == "encode" and self.spec:
            res["mfu_pct"] = round(
                100 * roofline.mfu(self.k, self.m, rate, self.spec), 2)
        if erased is not None:
            res["erased"] = erased
            res["chunks_read"] = n_read
            res["reconstructed_bytes"] = ops_per_step * len(erased) * self.chunk
            res["object_MBps"] = ops_per_step * self.k * self.chunk \
                / seconds_per_step / 1e6
        return res

    def run(self) -> dict:
        if self.args.workload == "encode":
            return self.encode()
        return self.decode()


def main(argv=None) -> dict:
    args = parse_args(argv)
    bench = ErasureCodeBench(args)
    res = bench.run()
    # Reference-format line: elapsed seconds <tab> throughput MB/s.
    print(f"{res['seconds']:.6f}\t{res['MB/s']:.2f}")
    if args.json or args.verbose:
        print(json.dumps(res))
    if args.perf_dump:
        from ceph_tpu.utils.perf_counters import PerfCountersCollection
        print(PerfCountersCollection.instance().dump_json())
    return res


if __name__ == "__main__":
    from ceph_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

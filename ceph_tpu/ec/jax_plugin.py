"""The ``plugin=jax`` erasure-code backend — RS encode/decode on TPU.

The north-star component: implements the ErasureCodeInterface contract with
GF(2^8) Reed-Solomon realized as batched binary matmuls on the MXU (or
nibble-LUT gathers on the VPU), replacing the reference's SIMD region kernels
(ref: src/erasure-code/isa/ErasureCodeIsa.cc ErasureCodeIsa;
src/erasure-code/jerasure/ErasureCodeJerasure.cc).

Per-erasure-pattern decode matrices are inverted once host-side and cached,
mirroring the reference's expanded-table cache
(ref: src/erasure-code/isa/ErasureCodeIsaTableCache.cc).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import matrix as rs
from ceph_tpu.ec.interface import ErasureCodeInterface, ErasureCodeProfile
from ceph_tpu.gf import ops, tables
from ceph_tpu.gf import pallas_kernels as pk
from ceph_tpu.utils.devmon import devmon as _devmon
from ceph_tpu.utils.logging import get_logger

log = get_logger("ec")


class _MatrixKernel:
    """A GF coding matrix compiled for the TPU formulations.

    backend "pallas" uses the fused unpack+matmul+pack kernel
    (gf.pallas_kernels) when the chunk length is tile-aligned, falling
    back to the XLA bitmatmul otherwise; the encode plan (bit-major
    permuted matrix + pack weights) is built host-side here, mirroring
    the reference's expanded-table construction at init
    (ref: src/erasure-code/isa/ErasureCodeIsa.cc prepare)."""

    def __init__(self, coeffs: np.ndarray, backend: str):
        self.coeffs = np.asarray(coeffs, dtype=np.uint8)
        self.backend = backend
        bm_np = tables.expand_bitmatrix(self.coeffs)
        self.bitmatrix = jnp.asarray(bm_np, dtype=jnp.int8)
        lo, hi = tables.nibble_tables(self.coeffs)
        self.lo = jnp.asarray(lo)
        self.hi = jnp.asarray(hi)
        self.plan = pk.make_plan(bm_np)

    def _pallas_ok(self, data) -> bool:
        rows_out, rows_in = self.coeffs.shape
        return pk.pallas_ok(int(data.shape[-1]), rows_in, rows_out)

    def apply(self, data: jax.Array) -> jax.Array:
        """(rows_in, L) uint8 -> (rows_out, L) uint8."""
        if self.backend == "lut":
            return ops.gf_matmul_lut(self.lo, self.hi, data)
        if self.backend == "pallas" and self._pallas_ok(data):
            return pk.encode_batch_planned(
                self.plan, data[None],
                interpret=jax.default_backend() != "tpu")[0]
        return ops.gf_matmul_bitplanes(self.bitmatrix, data)

    def apply_batch(self, data: jax.Array) -> jax.Array:
        """(batch, rows_in, C) -> (batch, rows_out, C)."""
        if self.backend == "pallas" and self._pallas_ok(data):
            return pk.encode_batch_planned(
                self.plan, data,
                interpret=jax.default_backend() != "tpu")
        return ops.encode_stripes(self.bitmatrix, self.lo, self.hi, data,
                                  backend="lut" if self.backend == "lut"
                                  else "bitmatmul")


class _BitmatrixKernel:
    """A raw GF(2) bitmatrix (array code) compiled for the MXU: operates
    on w packets per chunk (ref: jerasure bitmatrix techniques)."""

    def __init__(self, bm: np.ndarray, w: int):
        self.bm = jnp.asarray(np.asarray(bm, dtype=np.int8))
        self.w = w

    def apply_batch(self, data: jax.Array) -> jax.Array:
        """(batch, drives_in, C) -> (batch, drives_out, C); C % w == 0."""
        return ops.bitmatrix_encode_stripes(self.bm, data, self.w)

    def apply(self, data: jax.Array) -> jax.Array:
        return self.apply_batch(data[None])[0]


class ErasureCodeJax(ErasureCodeInterface):
    """plugin=jax k=K m=M technique= reed_sol_van | reed_sol_r6_op |
    cauchy_orig | cauchy_good | liberation | blaum_roth | liber8tion

    GF(2^8) techniques run as (8m)x(8k) bit-plane matmuls; the bitmatrix
    (array-code) techniques run as (2w)x(kw) packet-plane matmuls — both
    land on the MXU, so jerasure's XOR-schedule machinery (whose entire
    point is CPU XOR minimality) has no analog here by design."""

    DEFAULT_TECHNIQUE = "reed_sol_van"

    def __init__(self, profile: ErasureCodeProfile | str | None = None,
                 backend: str = "auto"):
        super().__init__()
        self.technique = self.DEFAULT_TECHNIQUE
        self.backend = backend
        self.w = 8
        self._bitmatrix = None
        self._encode_kernel = None
        self._decode_cache: dict[tuple, object] = {}
        self._decode_ref_cache: dict[tuple, np.ndarray] = {}
        self._fused_crc_cache: dict[int, object] = {}
        if profile is not None:
            self.init(ErasureCodeProfile.parse(profile))

    # -- lifecycle --------------------------------------------------------
    def init(self, profile: ErasureCodeProfile) -> None:
        self.profile = profile
        self.k = profile.get_int("k", 2)
        self.m = profile.get_int("m", 2)
        self.technique = profile.get("technique", self.DEFAULT_TECHNIQUE)
        self.backend = profile.get("backend", self.backend)
        if self.k < 1 or self.m < 1:
            raise ValueError(f"invalid geometry k={self.k} m={self.m}")
        if self.backend == "auto":
            # The fused pallas kernel wins on real TPUs (~103 GiB/s
            # encode at k=8,m=3 on v5e after the round-4 mod-2-absorb /
            # block-diag rewrite, vs ~60 for the XLA bitmatmul); on CPU
            # it only runs in slow interpret mode, so default to the
            # XLA path there.
            self.backend = ("pallas" if jax.default_backend() == "tpu"
                            else "bitmatmul")
        if self.backend not in ("bitmatmul", "lut", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"supported: bitmatmul, lut, pallas, auto")
        if self.technique in rs.BITMATRIX_TECHNIQUES:
            from ceph_tpu.ec import bitmatrix as bmx
            self.w = profile.get_int("w", 0) or bmx.default_w(
                self.technique, self.k)
            self._bitmatrix = bmx.bitmatrix_for(self.technique, self.k,
                                                self.m, self.w)
            self._encode_kernel = _BitmatrixKernel(self._bitmatrix, self.w)
        else:
            self.w = 8
            self._bitmatrix = None
            coeffs = rs.coding_matrix(self.technique, self.k, self.m)
            self._encode_kernel = _MatrixKernel(coeffs, self.backend)
        self._decode_cache.clear()
        self._decode_ref_cache.clear()
        self._fused_crc_cache.clear()
        log.dout(5, "init", k=self.k, m=self.m, technique=self.technique,
                 backend=self.backend)

    def get_alignment(self) -> int:
        # bitmatrix chunks are w packets; keep packets lane-aligned
        # (lcm, not product: w=8 already divides the lane width)
        import math

        from ceph_tpu.ec.interface import DEFAULT_ALIGNMENT
        if self._bitmatrix is not None:
            return DEFAULT_ALIGNMENT * self.w // math.gcd(
                DEFAULT_ALIGNMENT, self.w)
        return DEFAULT_ALIGNMENT

    def is_mds(self) -> bool:
        return True

    # -- encode -----------------------------------------------------------
    def encode_chunks(self, data: np.ndarray) -> np.ndarray:
        data = jnp.asarray(data, dtype=jnp.uint8)
        return np.asarray(self._encode_kernel.apply(data))

    def encode_batch(self, data: jax.Array) -> jax.Array:
        """Batched TPU path: (batch, k, C) uint8 -> (batch, m, C) parity.

        Stays on device; the benchmark and the sharded pipeline call
        this. First call per (kernel, shape) is compile-accounted
        through the device-runtime monitor (round 14) — a new batch
        shape recompiling under the OSD aggregator is a countable,
        traceable event now."""
        kern = self._encode_kernel
        return _devmon().jit_call(
            "ec_encode", (id(kern), tuple(data.shape)),
            kern.apply_batch, data)

    def encode_batch_reference(self, data):
        """Host-only bit-exact reference encode — the last rung of the
        OSD aggregator's degrade ladder. Pure numpy, no jit, no
        device: ``gf_matmul_np`` (the numpy oracle both JAX kernels
        are pinned against) for the GF(2^8) techniques, and the
        packet-plane XOR mirror of ``bitmatrix_encode_stripes`` for
        the array codes. (B, k, C) uint8 -> (B, m, C)."""
        data = np.ascontiguousarray(np.asarray(data), dtype=np.uint8)
        B, k, C = data.shape
        if self._bitmatrix is not None:
            w = self.w
            ps = C // w
            bm = np.asarray(self._bitmatrix) != 0         # (mw, kw)
            planes = data.reshape(B, k * w, ps)
            flat = planes.transpose(1, 0, 2).reshape(k * w, B * ps)
            out = np.zeros((bm.shape[0], B * ps), dtype=np.uint8)
            for r in range(bm.shape[0]):
                sel = flat[bm[r]]
                if sel.shape[0]:
                    out[r] = np.bitwise_xor.reduce(sel, axis=0)
            mw = out.shape[0]
            return out.reshape(mw, B, ps).transpose(1, 0, 2).reshape(
                B, mw // w, C)
        coeffs = self._encode_kernel.coeffs
        x = data.transpose(1, 0, 2)                       # (k, B, C)
        return tables.gf_matmul_np(coeffs, x).transpose(1, 0, 2)

    def encode_batch_with_crc(self, data):
        """Fused checksum+encode: ONE jitted device program computes
        the parity AND a raw-CRC32 per shard row (data rows included).

        (B, k, C) uint8 -> (parity (B, m, C), row_crcs (B, k+m) u32).
        The CRC leg is the (rows, 8C) @ (8C, 32) GF(2) bit matmul of
        ec.crc.row_crc_matrix — same MXU bit-plane idiom as the encode
        itself; the per-shard combine over a write's rows is O(rows)
        32-bit host work in ec.crc (the O(bytes) part lives here)."""
        from ceph_tpu.ec import crc as _crc

        data = jnp.asarray(data, dtype=jnp.uint8)
        C = int(data.shape[-1])
        fused = self._fused_crc_cache.get(C)
        if fused is None:
            G = jnp.asarray(_crc.row_crc_matrix(C))       # (8C, 32) i8
            kern = self._encode_kernel
            n = self.k + self.m

            def _fused(d):
                parity = kern.apply_batch(d)
                word = jnp.concatenate(
                    [d, parity.astype(jnp.uint8)], axis=1)  # (B, n, C)
                rows = word.reshape(-1, C)
                # one bit-PLANE at a time: (rows, C) @ (C, 32) per
                # plane keeps the matmul operand at word-bytes size —
                # the naive (rows, 8C) bit expansion is 8x the batch
                # (~1.4 GiB at the osd_ec_agg_max_stripes ceiling on
                # the production shape) and would break that knob's
                # memory-bound promise. G row 8p+b is byte p, bit b
                # (LSB-first, matching row_crc_matrix), so plane b
                # multiplies G[b::8].
                acc = jnp.zeros((rows.shape[0], 32), dtype=jnp.int32)
                for b in range(8):
                    plane = ((rows >> jnp.uint8(b)) &
                             jnp.uint8(1)).astype(jnp.int8)
                    acc = acc + jnp.matmul(
                        plane, G[b::8, :],
                        preferred_element_type=jnp.int32)
                bit32 = (acc & 1).astype(jnp.uint32)
                weights = jnp.uint32(1) << jnp.arange(
                    32, dtype=jnp.uint32)
                crcs = jnp.sum(bit32 * weights[None, :], axis=1,
                               dtype=jnp.uint32)
                return parity, crcs.reshape(-1, n)

            fused = self._fused_crc_cache[C] = jax.jit(_fused)
        return _devmon().jit_call(
            "ec_encode_crc", (id(fused), tuple(data.shape)),
            fused, data)

    # -- decode -----------------------------------------------------------
    def _decode_kernel(self, avail: tuple[int, ...],
                       want: tuple[int, ...]):
        key = (avail, want)
        kern = self._decode_cache.get(key)
        if kern is None:
            if self._bitmatrix is not None:
                from ceph_tpu.ec import bitmatrix as bmx
                d = bmx.decode_bitmatrix(self._bitmatrix, self.k, self.m,
                                         self.w, avail, want)
                kern = _BitmatrixKernel(d, self.w)
            else:
                d = rs.decode_matrix(self.technique, self.k, self.m,
                                     avail, want)
                kern = _MatrixKernel(d, self.backend)
            self._decode_cache[key] = kern
            log.dout(10, "decode matrix built", avail=avail, want=want)
        return kern

    def decode_chunks(self, want: Sequence[int],
                      chunks: Mapping[int, np.ndarray]) -> dict[int, np.ndarray]:
        avail = tuple(sorted(chunks))[:self.k]
        if len(avail) < self.k:
            raise ValueError(
                f"cannot decode: have {len(chunks)} chunks, need {self.k}")
        want_t = tuple(want)
        kern = self._decode_kernel(avail, want_t)
        stacked = jnp.stack(
            [jnp.asarray(chunks[i], dtype=jnp.uint8) for i in avail])
        out = np.asarray(kern.apply(stacked))
        return {c: out[i] for i, c in enumerate(want_t)}

    def decode_batch(self, want: Sequence[int], avail: Sequence[int],
                     chunks: jax.Array) -> jax.Array:
        """Batched decode: chunks (batch, len(avail), C) -> (batch, len(want), C)."""
        kern = self._decode_kernel(tuple(avail), tuple(want))
        return _devmon().jit_call(
            "ec_decode", (id(kern), tuple(chunks.shape)),
            kern.apply_batch, chunks)

    def decode_batch_reference(self, want: Sequence[int],
                               avail: Sequence[int], chunks):
        """Host-only bit-exact reference decode — the last rung of the
        OSD read aggregator's degrade ladder. Pure numpy, no jit, no
        device: the same per-erasure-pattern matrix inversion the
        device path caches, applied with ``gf_matmul_np`` (GF(2^8)
        techniques) or the packet-plane XOR mirror (array codes).
        (B, len(avail), C) uint8 -> (B, len(want), C)."""
        chunks = np.ascontiguousarray(np.asarray(chunks), dtype=np.uint8)
        B, _, C = chunks.shape
        key = (tuple(avail), tuple(want))
        d = self._decode_ref_cache.get(key)
        if d is None:
            if self._bitmatrix is not None:
                from ceph_tpu.ec import bitmatrix as bmx
                d = bmx.decode_bitmatrix(self._bitmatrix, self.k, self.m,
                                         self.w, key[0], key[1])
            else:
                d = rs.decode_matrix(self.technique, self.k, self.m,
                                     key[0], key[1])
            self._decode_ref_cache[key] = np.asarray(d, dtype=np.uint8)
            d = self._decode_ref_cache[key]
        if self._bitmatrix is not None:
            w = self.w
            ps = C // w
            bm = d != 0                        # (len(want)*w, len(avail)*w)
            planes = chunks.reshape(B, -1, ps)
            flat = planes.transpose(1, 0, 2).reshape(-1, B * ps)
            out = np.zeros((bm.shape[0], B * ps), dtype=np.uint8)
            for r in range(bm.shape[0]):
                sel = flat[bm[r]]
                if sel.shape[0]:
                    out[r] = np.bitwise_xor.reduce(sel, axis=0)
            ww = out.shape[0]
            return out.reshape(ww, B, ps).transpose(1, 0, 2).reshape(
                B, ww // w, C)
        x = chunks.transpose(1, 0, 2)          # (len(avail), B, C)
        return tables.gf_matmul_np(d, x).transpose(1, 0, 2)


def _resident_perf():
    """Per-OSD counter family for the hot-shard residency cache
    (register=False: several in-process OSDs each own one; they reach
    prometheus through the daemon->mgr report path as
    ``ceph_osd_ec_resident_*`` rows)."""
    from ceph_tpu.utils.perf_counters import PerfCountersBuilder
    return (
        PerfCountersBuilder("osd_ec_resident")
        .add_u64_counter("hits",
                         "gathers served from the device-resident "
                         "cache (no subreads, no decode, no H2D)")
        .add_u64_counter("misses", "gathers that went to the shards")
        .add_u64_counter("inserts", "stripe ranges staged resident")
        .add_u64_counter("evictions",
                         "LRU evictions under osd_ec_resident_bytes")
        .add_u64_counter("invalidations",
                         "entries dropped by a write to their object")
        .add_u64_counter("rejected",
                         "ranges larger than the whole budget, never "
                         "cached")
        .add_u64("resident_bytes", "bytes currently resident (gauge)")
        .add_u64("entries", "entries currently resident (gauge)")
        .create_perf_counters(register=False))


class DeviceShardCache:
    """Bounded device-side LRU of gathered stripe ranges — hot-shard
    residency for the OSD data path (round 19).

    A read-modify-write or a repeated degraded read used to re-gather
    the same stripes (k subread round-trips + a decode + an H2D stage)
    every time. This cache pins the gathered (count, k, C) batch in
    device memory under an ``osd_ec_resident_bytes`` budget, keyed by
    (PG, object, stripe range, object VERSION) — the same write-time
    ``_v`` discipline the shards carry, so any write bumps the version
    and makes every cached generation of that object unreachable.
    Explicit ``invalidate`` on sub-write apply reclaims those dead
    entries eagerly instead of waiting for LRU pressure.

    Entries are immutable by contract: ``get`` returns the stored
    device array; callers read through ``np.asarray`` or feed it to a
    device kernel, never mutate it in place.
    """

    def __init__(self, config: dict | None = None):
        self.config = config if config is not None else {}
        self.perf = _resident_perf()
        # key -> (device array, nbytes); insertion order = LRU order
        self._lru: "dict[tuple, tuple[object, int]]" = {}
        self._bytes = 0

    # knobs (read LIVE: shrinking the budget takes effect on the next
    # insert's eviction sweep; 0 disables lookups AND inserts)
    def budget(self) -> int:
        return int(self.config.get("osd_ec_resident_bytes", 64 << 20))

    def enabled(self) -> bool:
        return self.budget() > 0

    def get(self, key: tuple):
        if not self.enabled():
            return None
        ent = self._lru.get(key)
        if ent is None:
            self.perf.inc("misses")
            return None
        # move-to-end = most recently used
        del self._lru[key]
        self._lru[key] = ent
        self.perf.inc("hits")
        return ent[0]

    def put(self, key: tuple, host_array) -> None:
        if not self.enabled() or key in self._lru:
            return
        # explicit copy: jax.device_put may alias an aligned host
        # buffer on the CPU backend, and callers keep (and may write
        # through copies of) the array they handed us
        arr = np.array(host_array, dtype=np.uint8, order="C")
        nbytes = int(arr.nbytes)
        budget = self.budget()
        if nbytes > budget:
            self.perf.inc("rejected")
            return
        while self._bytes + nbytes > budget and self._lru:
            old_key = next(iter(self._lru))
            _, old_n = self._lru.pop(old_key)
            self._bytes -= old_n
            self.perf.inc("evictions")
        try:
            dev = jax.device_put(arr)
        except Exception as e:
            log.dout(1, f"resident cache device_put failed "
                        f"({type(e).__name__}: {str(e)[:200]})")
            return
        self._lru[key] = (dev, nbytes)
        self._bytes += nbytes
        self.perf.inc("inserts")
        self._gauges()

    def invalidate(self, *prefix) -> int:
        """Drop every entry whose key starts with ``prefix`` (e.g.
        (pgid, oid) on a sub-write apply). Version-keying already makes
        stale generations unreachable; this reclaims their bytes."""
        n = 0
        for key in [k for k in self._lru if k[:len(prefix)] == prefix]:
            _, nbytes = self._lru.pop(key)
            self._bytes -= nbytes
            n += 1
        if n:
            self.perf.inc("invalidations", n)
            self._gauges()
        return n

    def clear(self) -> None:
        self._lru.clear()
        self._bytes = 0
        self._gauges()

    def _gauges(self) -> None:
        self.perf.set("resident_bytes", self._bytes)
        self.perf.set("entries", len(self._lru))

    def dump(self) -> dict:
        d = self.perf.dump()
        return {
            "enabled": self.enabled(),
            "budget_bytes": self.budget(),
            "resident_bytes": self._bytes,
            "entries": len(self._lru),
            "hits": d.get("hits", 0),
            "misses": d.get("misses", 0),
            "inserts": d.get("inserts", 0),
            "evictions": d.get("evictions", 0),
            "invalidations": d.get("invalidations", 0),
        }


class StreamingEncodePipeline:
    """Double-buffered H2D/D2H streaming encode.

    The resident benchmark number assumes the stripes already live in
    HBM; a real ingest path pays host->device per batch. This pipeline
    overlaps the three legs so a real host measures the PCIe-bound
    rate instead of the dispatch-serialized one:

    - **H2D of batch N+1** (``jax.device_put``, asynchronous) is issued
      BEFORE batch N's encode is dispatched, so the transfer engine
      fills the next buffer while the MXU works;
    - **encode of batch N** runs under a jit whose input buffer is
      DONATED on TPU (``donate_argnums``) — with two in-flight host
      batches the donated buffers alternate ping/pong, so steady state
      holds two staging buffers instead of allocating per step;
    - **D2H of batch N-1** (the ``np.asarray`` readback) blocks the
      host while batch N executes — in-order device execution makes
      the previous result's readback the natural overlap window.

    Donation is gated to the TPU backend: the CPU runtime ignores
    donations with a per-call warning, which would spam every streamed
    smoke run.
    """

    def __init__(self, ec: ErasureCodeJax, donate: bool | None = None):
        self.ec = ec
        if donate is None:
            donate = jax.default_backend() == "tpu"
        kern = ec._encode_kernel
        self._kern = kern
        self._fn = jax.jit(kern.apply_batch,
                           donate_argnums=(0,) if donate else ())
        # lazily-built non-donated fallback jit (see encode_iter)
        self._plain_fn = None

    def _encode_plain(self, host, dm):
        """The non-donated unpipelined fallback: stage, encode, read
        back — one batch at a time, no buffer donation, no overlap."""
        if self._plain_fn is None:
            self._plain_fn = jax.jit(self._kern.apply_batch)
        fn = self._plain_fn
        out = dm.jit_call("ec_stream_encode",
                          (id(fn), tuple(host.shape)), fn, host)
        host_out = np.asarray(out)
        dm.record_d2h(host_out.nbytes)
        return host_out

    def encode_iter(self, batches):
        """host (B, k, C) uint8 batches in -> parity np arrays out,
        transfer of batch N+1 overlapped with encode of batch N.

        Transfer accounting (round 14): every H2D stage and D2H
        readback feeds the device-runtime monitor's byte counters, so
        a pipeline-bound ingest shows up as transfer GiB in
        `device-runtime status` instead of as unexplained wall.

        Fault discipline (round 16): a transfer/encode failure
        mid-pipeline does NOT lose batches — every staged host batch
        is kept until its parity is yielded, so on failure the
        pipeline falls back to the non-donated unpipelined path,
        re-encodes the in-flight batches from their host copies and
        drains the rest of the iterator (devmon counts a
        ``stream_fallbacks``)."""
        dm = _devmon()

        def _encode(batch):
            return dm.jit_call("ec_stream_encode",
                               (id(self._fn), tuple(batch.shape)),
                               self._fn, batch)

        def _readback(parity):
            host = np.asarray(parity)
            dm.record_d2h(host.nbytes)
            return host

        it = iter(batches)
        # host copies of staged batches whose parity has NOT been
        # yielded yet, oldest first — the fallback's replay source
        pending: list[np.ndarray] = []
        try:
            try:
                first = np.ascontiguousarray(next(it))
            except StopIteration:
                return
            pending.append(first)
            dm.record_h2d(first.nbytes)
            dm.note_staging(first.nbytes)
            cur = jax.device_put(first)
            prev = None
            for nxt_host in it:
                nxt_host = np.ascontiguousarray(nxt_host)
                pending.append(nxt_host)
                dm.record_h2d(nxt_host.nbytes)
                nxt = jax.device_put(nxt_host)
                out = _encode(cur)
                if prev is not None:
                    yield _readback(prev)
                    pending.pop(0)
                prev, cur = out, nxt
            out = _encode(cur)
            if prev is not None:
                yield _readback(prev)
                pending.pop(0)
            yield _readback(out)
            pending.pop(0)
        except Exception as e:
            dm.perf.inc("stream_fallbacks")
            log.dout(0, f"streaming encode pipeline failed "
                        f"({type(e).__name__}: {str(e)[:200]}) — "
                        f"falling back to the unpipelined path for "
                        f"{len(pending)} in-flight batches + the rest")
            for host in pending:
                yield self._encode_plain(host, dm)
            for nxt_host in it:
                host = np.ascontiguousarray(nxt_host)
                dm.record_h2d(host.nbytes)
                yield self._encode_plain(host, dm)

    def encode_all(self, batches) -> list:
        return list(self.encode_iter(batches))

    def encode_payload_iter(self, payloads, k: int, chunk_size: int):
        """Messenger-ingest handoff: wire-frame payload buffers in,
        parity out, with NO intermediate host staging copy.

        Each payload is whatever the messenger delivered for a write —
        ``bytes`` or, on the zero-copy decode path (denc blob_view), a
        ``memoryview`` over the received frame — whose length is a
        multiple of the stripe width k*chunk_size. ``np.frombuffer``
        wraps the buffer in place and the reshape is a view, so the
        bytes go wire frame -> H2D stage (encode_iter's device_put)
        directly; the old path staged a full ``bytes`` copy first."""
        W = k * chunk_size

        def _carve():
            for p in payloads:
                arr = np.frombuffer(p, dtype=np.uint8)
                if arr.size % W:
                    raise ValueError(
                        f"payload of {arr.size} bytes is not a whole "
                        f"number of {W}-byte stripes")
                yield arr.reshape(-1, k, chunk_size)
        return self.encode_iter(_carve())

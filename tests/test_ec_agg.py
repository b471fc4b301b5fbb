"""Round 13: the EC data path at production traffic — the OSD-side
cross-op encode aggregator, the fused checksum+encode program, and the
double-buffered streaming pipeline.

ref test model: the per-op vs batched equivalence discipline of
PR 10's sharded-sweep tests + src/test/osd EC determinism pins. Units
only (the live-cluster acceptance rides tests/test_ec_cluster.py):

- **CRC algebra** — the GF(2) decomposition ec/crc.py stands on:
  ``raw`` linearity, the length-only affine split, the per-row bit
  matrix vs zlib, the row->shard combine, and the two ``hcrc_attr``
  producers (fused row CRCs vs host zlib) byte-for-byte equal;
- **fused encode+CRC** — one device program returns the SAME parity as
  the plain kernel plus per-row CRCs that fold to ``zlib.crc32`` of
  every shard (data AND parity positions);
- **aggregator, both directions** — the policy exists once
  (``osd/ec_aggregator._WindowedBatcher``), so its cases run once per
  direction (the ``way`` fixture: ``ECAggregator`` encoding,
  ``ECReadAggregator`` decoding): concurrent ops coalesce into fewer
  launches with lane-for-lane identical results, every flush trigger
  fires (full/window/idle, a lone op never held past the window, a
  cancelled flusher flushes as ``window``), the ``=off`` baseline
  bypasses UNPADDED, padding is pow2-bounded, drain cancels cleanly,
  and a failed batched flush rejects ONLY its own poisoned waiter;
  what belongs to the decode side alone is in tests/test_ec_read_agg.py;
- **pipeline** — StreamingEncodePipeline's outputs equal per-batch
  encodes, in order;
- **degrade ladder (round 16)** — per-op device retries are bounded,
  the host reference encoder serves bit-exactly as the last rung, the
  fused checksum+encode jit quarantines on backoff after failures, and
  the streaming pipeline falls back to the unpipelined path without
  losing a batch.

One module-scoped plugin instance: every test shares its jit cache
(tier-1 runs near the wall-clock cap — compiles are the budget).
"""

import asyncio
import time
import zlib

import numpy as np
import pytest

from ceph_tpu.ec import crc as ec_crc
from ceph_tpu.ec.interface import ErasureCodeInterface
from ceph_tpu.ec.jax_plugin import ErasureCodeJax, StreamingEncodePipeline
from ceph_tpu.osd.ec_aggregator import ECAggregator, ECReadAggregator

K, M, C = 3, 2, 64
N = K + M
WANT = (0,)             # data chunk 0 lost
AVAIL = (1, 2, 3)       # survivors: data 1..2 + parity 0


@pytest.fixture(scope="module")
def ec():
    return ErasureCodeJax(
        f"plugin=jax k={K} m={M} technique=reed_sol_van")


def _rng(seed=13):
    return np.random.default_rng(seed)


def run(coro):
    return asyncio.run(coro)


# -- CRC algebra (host-side; the facts the fused pass stands on) -----------

def test_raw_crc_linearity_and_affine_split():
    """``raw`` is GF(2)-linear in the message bits; zlib.crc32 is raw
    plus a length-only constant; raw composes through its own state."""
    rng = _rng(1)
    for ln in (1, 7, 64, 513):
        a = rng.integers(0, 256, ln, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, ln, dtype=np.uint8).tobytes()
        x = bytes(p ^ q for p, q in zip(a, b))
        assert ec_crc.raw_crc(x) == \
            ec_crc.raw_crc(a) ^ ec_crc.raw_crc(b)
        assert zlib.crc32(a) == \
            ec_crc.raw_crc(a) ^ zlib.crc32(b"\x00" * ln)
        assert ec_crc.raw_crc(a + b) == \
            ec_crc.raw_crc(b, ec_crc.raw_crc(a))
    # the affine constant comes from O(log n) operator squaring, not
    # a length-sized zero buffer — pin it against zlib across scales
    for ln in (0, 1, 513, 65537, 1 << 20):
        assert ec_crc._zero_crc(ln) == zlib.crc32(b"\x00" * ln), ln


def test_row_crc_matrix_matches_zlib():
    """The (8C, 32) GF(2) matrix applied to a row's bits (LSB-first
    per byte) IS the row's raw CRC — the device leg of the fusion."""
    rng = _rng(2)
    G = ec_crc.row_crc_matrix(C)
    assert G.shape == (8 * C, 32)
    for _ in range(4):
        row = rng.integers(0, 256, C, dtype=np.uint8)
        bits = ((row[:, None] >> np.arange(8)) & 1).reshape(-1)
        acc = (bits.astype(np.int64) @ G.astype(np.int64)) & 1
        val = int((acc.astype(np.uint64) <<
                   np.arange(32, dtype=np.uint64)).sum())
        assert val == ec_crc.raw_crc(row.tobytes())


def test_hcrc_attr_producers_agree_byte_for_byte():
    """The unified ``_hcrc`` helper's two producers — device row CRCs
    folded through the combine vs host ``zlib.crc32`` — agree on the
    full attribute bytes for multi-row shards of several lengths."""
    rng = _rng(3)
    for count in (1, 2, 5, 16):
        rows = rng.integers(0, 256, (count, C), dtype=np.uint8)
        shard = rows.tobytes()
        row_crcs = np.array(
            [ec_crc.raw_crc(r.tobytes()) for r in rows],
            dtype=np.uint32)
        assert int(ec_crc.shard_crc32(row_crcs, C)) == \
            zlib.crc32(shard), count
        assert ec_crc.hcrc_attr(shard, row_crcs=row_crcs,
                                chunk_size=C) == \
            ec_crc.hcrc_attr(shard) == \
            zlib.crc32(shard).to_bytes(4, "little")


# -- fused checksum+encode -------------------------------------------------

def test_fused_encode_crc_bit_exact(ec):
    """One device program: parity identical to the plain kernel, and
    the per-row CRCs fold to zlib.crc32 of EVERY shard position's
    bytes (data and parity) — the acceptance pin for the fused
    ``_hcrc`` stamps."""
    rng = _rng(4)
    data = rng.integers(0, 256, (5, K, C), dtype=np.uint8)
    parity, crcs = ec.encode_batch_with_crc(data)
    parity, crcs = np.asarray(parity), np.asarray(crcs)
    assert (parity == np.asarray(ec.encode_batch(data))).all()
    assert crcs.shape == (5, N) and crcs.dtype == np.uint32
    word = np.concatenate([data, parity], axis=1)
    for pos in range(N):
        shard = word[:, pos, :].tobytes()     # the ec_pg shard layout
        assert ec_crc.hcrc_attr(shard, row_crcs=crcs[:, pos],
                                chunk_size=C) == \
            zlib.crc32(shard).to_bytes(4, "little"), pos


def test_base_interface_fused_is_optional():
    """A plugin without a fused path returns ``(parity, None)`` from
    the base ``encode_batch_with_crc`` — callers fall back to host
    zlib via hcrc_attr (the aggregator then hands back None CRCs)."""
    from ceph_tpu.ec.lrc import ErasureCodeLrc
    lrc = ErasureCodeLrc("plugin=lrc k=4 m=2 l=3")
    assert lrc.encode_batch_with_crc.__func__ is \
        ErasureCodeInterface.encode_batch_with_crc
    rng = _rng(5)
    data = rng.integers(0, 256, (2, 4, 32), dtype=np.uint8)
    parity, crcs = lrc.encode_batch_with_crc(data)
    assert crcs is None
    assert (np.asarray(parity) ==
            np.asarray(lrc.encode_batch(data))).all()

    async def go():
        agg = ECAggregator({"osd_ec_agg": True})
        p, c = await agg.encode(lrc, data, with_crc=True)
        assert c is None
        assert (p == np.asarray(lrc.encode_batch(data))).all()
    run(go())


# -- the aggregator, once per direction ------------------------------------

class _Encode:
    """The encode direction, as the shared cases drive it."""

    cls, opt = ECAggregator, "osd_ec_agg"

    @staticmethod
    def ops(ec, rng, sizes):
        return [rng.integers(0, 256, (b, K, C), dtype=np.uint8)
                for b in sizes]

    @staticmethod
    def submit(agg, ec, x, with_crc=False):
        return agg.encode(ec, x, with_crc=with_crc)

    @staticmethod
    def run(agg, ec, x, **kw):
        return agg._run(ec, x, True, **kw)

    @staticmethod
    def direct(ec, x):
        return np.asarray(ec.encode_batch(x))

    @staticmethod
    def rows(out):
        return np.asarray(out[0])


class _Decode:
    """The decode direction: survivor chunks in, the lost chunk out."""

    cls, opt = ECReadAggregator, "osd_ec_read_agg"

    @staticmethod
    def ops(ec, rng, sizes):
        out = []
        for data in _Encode.ops(ec, rng, sizes):
            word = np.concatenate(
                [data, np.asarray(ec.encode_batch(data))], axis=1)
            out.append(np.stack([word[:, i, :] for i in AVAIL], axis=1))
        return out

    @staticmethod
    def submit(agg, ec, x, with_crc=False):
        return agg.decode(ec, WANT, AVAIL, x)

    @staticmethod
    def run(agg, ec, x, **kw):
        return agg._run(ec, WANT, AVAIL, x, **kw)

    @staticmethod
    def direct(ec, x):
        return np.asarray(ec.decode_batch(WANT, AVAIL, x))

    @staticmethod
    def rows(out):
        return np.asarray(out)


@pytest.fixture(params=[_Encode, _Decode], ids=["encode", "decode"])
def way(request):
    return request.param


def _cfg(way, **knobs):
    """``{<prefix>: True, <prefix>_<knob>: value, ...}``"""
    return {way.opt: True,
            **{f"{way.opt}_{k}": v for k, v in knobs.items()}}


class _Spy:
    """Records the batch size of every device launch."""

    profile = "spy"

    def __init__(self, ec):
        self._ec = ec
        self.launched = []

    def encode_batch(self, data):
        self.launched.append(data.shape[0])
        return self._ec.encode_batch(data)

    def encode_batch_with_crc(self, data):
        self.launched.append(data.shape[0])
        return self._ec.encode_batch_with_crc(data)

    def decode_batch(self, want, avail, chunks):
        self.launched.append(chunks.shape[0])
        return self._ec.decode_batch(want, avail, chunks)


def test_aggregator_coalesces_bit_exact(ec, way):
    """Concurrent ops (non-pow2 sizes, mixed with_crc where encoding)
    coalesce into FEWER launches than ops, and every op's slice equals
    its own per-op result lane for lane — the bit-exactness contract."""
    ops = way.ops(ec, _rng(6), (1, 3, 2, 5, 1, 3, 2))

    async def go():
        agg = way.cls(_cfg(way, window_us=2000.0))
        outs = await asyncio.gather(*[
            way.submit(agg, ec, d, with_crc=(i % 2 == 0))
            for i, d in enumerate(ops)])
        d = agg.dump()
        assert 1 <= d["batches"] < len(ops)
        assert d["ops"] == len(ops)
        assert d["stripes"] == sum(o.shape[0] for o in ops)
        for i, (dat, out) in enumerate(zip(ops, outs)):
            assert (way.rows(out) == way.direct(ec, dat)).all(), i
            if way is not _Encode:
                continue
            p, c = out
            if i % 2 == 0:
                word = np.concatenate(
                    [dat, np.asarray(p)], axis=1)
                for pos in range(N):
                    assert ec_crc.hcrc_attr(
                        word[:, pos, :].tobytes(),
                        row_crcs=c[:, pos], chunk_size=C) == \
                        ec_crc.hcrc_attr(word[:, pos, :].tobytes())
            else:
                assert c is None, i
    run(go())


def test_aggregator_full_trigger(ec, way):
    """``<prefix>_max_stripes`` forces an immediate flush — the
    batch-size ceiling fires before any window elapses."""
    ops = way.ops(ec, _rng(7), (2, 2, 2, 2))

    async def go():
        agg = way.cls(_cfg(way, window_us=1e6, max_stripes=4))
        t0 = asyncio.get_event_loop().time()
        await asyncio.gather(*[way.submit(agg, ec, d) for d in ops])
        took = asyncio.get_event_loop().time() - t0
        d = agg.dump()
        assert d["flushes"]["full"] >= 1
        assert took < 1.0      # nobody waited for the 1s window
    run(go())


def test_aggregator_lone_op_never_held_past_window(ec, way):
    """A lone op flushes EARLY on queue idleness — and in any case
    inside the window (here 10s, so a window-bound wait would hang
    the assertion far past the observed bound)."""
    d, = way.ops(ec, _rng(8), (1,))

    async def go():
        agg = way.cls(_cfg(way, window_us=10e6))
        t0 = asyncio.get_event_loop().time()
        out = await way.submit(agg, ec, d)
        took = asyncio.get_event_loop().time() - t0
        assert (way.rows(out) == way.direct(ec, d)).all()
        assert took < 9.0, "lone op pinned to the window"
        assert agg.dump()["flushes"]["idle"] == 1
    run(go())


def test_aggregator_window_trigger(ec, way):
    """An expired window flushes whatever accumulated (window ~0:
    the first flusher wake is already past the deadline)."""
    ops = way.ops(ec, _rng(9), (1, 1))

    async def go():
        agg = way.cls(_cfg(way, window_us=0.0))
        await asyncio.gather(*[way.submit(agg, ec, d) for d in ops])
        assert agg.dump()["flushes"]["window"] >= 1
    run(go())


def test_aggregator_cancelled_flusher_flushes_as_window(ec, way):
    """A flusher task cancelled from outside once it runs (its owner
    going down, a task group unwinding) must not strand its waiters:
    it flushes its group as ``window`` on the way out."""
    d, = way.ops(ec, _rng(21), (3,))

    async def go():
        agg = way.cls(_cfg(way, window_us=10e6))
        waiter = asyncio.ensure_future(way.submit(agg, ec, d))
        for _ in range(3):              # entry lands, flusher soaking
            await asyncio.sleep(0)
        g, = agg._groups.values()
        assert not waiter.done()
        g.task.cancel()
        out = await asyncio.wait_for(waiter, timeout=60.0)
        assert (way.rows(out) == way.direct(ec, d)).all()
        dmp = agg.dump()
        assert dmp["flushes"] == {"window": 1, "full": 0, "idle": 0}
        assert dmp["pending_ops"] == 0 and dmp["pending_groups"] == 0
    run(go())


def test_aggregator_off_is_per_op_baseline(ec, way):
    """``<prefix>=off`` (read LIVE) serves every op per-op and
    UNPADDED: no batches, a bypass count, identical results — the
    measured baseline the bench compares against."""
    ops = way.ops(ec, _rng(10), (3, 3, 3))
    spy = _Spy(ec)

    async def go():
        cfg = {way.opt: False}
        agg = way.cls(cfg)
        for d in ops:
            out = await way.submit(agg, spy, d, with_crc=True)
            assert (way.rows(out) == way.direct(ec, d)).all()
            if way is _Encode:
                assert out[1] is not None   # fusion is orthogonal to agg
        dmp = agg.dump()
        assert dmp["batches"] == 0 and dmp["bypass"] == len(ops)
        assert dmp["enabled"] is False
        assert spy.launched == [3, 3, 3]    # UNPADDED per-op launches
        # live flip back on: the same instance coalesces again
        cfg[way.opt] = True
        await asyncio.gather(*[way.submit(agg, ec, d) for d in ops])
        assert agg.dump()["batches"] >= 1
    run(go())


def test_aggregator_pads_to_pow2(ec, way):
    """Padded launch sizes bound the jit cache to O(log max_batch)
    shapes, and the pad rows never leak into results."""
    for b, want in ((1, 1), (2, 2), (3, 4), (5, 8), (9, 16),
                    (4096, 4096)):
        assert way.cls._pad(b) == want, b
    agg = way.cls({})
    d, = way.ops(ec, _rng(11), (5,))    # pads to 8
    spy = _Spy(ec)
    out = way.run(agg, spy, d)
    assert spy.launched == [8]          # flush path pads 5 -> 8
    assert way.rows(out).shape[0] == 5
    assert (way.rows(out) == way.direct(ec, d)).all()
    if way is _Encode:
        assert out[0].shape == (5, M, C) and out[1].shape == (5, N)
    # the <prefix>=off bypass is the UNPADDED historical per-op
    # launch — the measured baseline must not pay pad compute the
    # pre-aggregator path never paid
    out2 = way.run(agg, spy, d, pad=False)
    assert spy.launched == [8, 5]
    assert (way.rows(out2) == way.rows(out)).all()


def test_aggregator_drain_cancels_waiters(ec, way):
    """Daemon stop: pending waiters are CANCELLED (their PG op workers
    are going down too), timers die, and the stopped aggregator serves
    later stragglers per-op instead of queueing them forever."""
    d, = way.ops(ec, _rng(12), (1,))

    async def go():
        agg = way.cls(_cfg(way, window_us=10e6, max_stripes=1 << 20))
        waiter = asyncio.ensure_future(way.submit(agg, ec, d))
        await asyncio.sleep(0)          # entry lands, timer armed
        assert agg.drain() == 1
        with pytest.raises(asyncio.CancelledError):
            await waiter
        assert agg.dump()["pending_ops"] == 0
        out = await way.submit(agg, ec, d)      # straggler: per-op
        assert (way.rows(out) == way.direct(ec, d)).all()
    run(go())


# -- the double-buffered streaming pipeline --------------------------------

def test_streaming_pipeline_matches_per_batch(ec):
    """Pipelined outputs equal per-batch encodes, in submission
    order; zero- and one-batch streams behave."""
    rng = _rng(14)
    batches = [rng.integers(0, 256, (2, K, C), dtype=np.uint8)
               for _ in range(5)]
    pipe = StreamingEncodePipeline(ec)
    outs = pipe.encode_all([b.copy() for b in batches])
    assert len(outs) == len(batches)
    for i, (b, o) in enumerate(zip(batches, outs)):
        assert (np.asarray(o) ==
                np.asarray(ec.encode_batch(b))).all(), i
    assert pipe.encode_all([]) == []
    one = pipe.encode_all([batches[0].copy()])
    assert len(one) == 1 and (
        np.asarray(one[0]) ==
        np.asarray(ec.encode_batch(batches[0]))).all()


# -- the degrade ladder (round 16) -----------------------------------------

class _FlakyEC:
    """Delegates to the module plugin but fails on command, in both
    directions: device launches raise while a ``poison`` stripe rides
    in the batch (or always, with ``fail_all``), and the reference
    refuses the poison stripe itself — the worst case the ladder must
    isolate."""

    profile = "flaky"

    def __init__(self, ec, poison=None, fail_all=False):
        self._ec = ec
        self._poison = poison
        self.fail_all = fail_all
        self.device_calls = 0

    def _poisoned(self, data):
        return self._poison is not None and bool(
            (np.asarray(data) == self._poison).all(axis=(1, 2)).any())

    def _maybe_fail(self, data):
        self.device_calls += 1
        if self.fail_all or self._poisoned(data):
            raise RuntimeError("injected device failure")

    def _reference_refuses(self, data):
        if self._poisoned(data):
            raise RuntimeError("reference refuses the poison stripe")

    def encode_batch(self, data):
        self._maybe_fail(data)
        return self._ec.encode_batch(data)

    def encode_batch_with_crc(self, data):
        self._maybe_fail(data)
        return self._ec.encode_batch_with_crc(data)

    def encode_batch_reference(self, data):
        self._reference_refuses(data)
        return self._ec.encode_batch_reference(data)

    def decode_batch(self, want, avail, chunks):
        self._maybe_fail(chunks)
        return self._ec.decode_batch(want, avail, chunks)

    def decode_batch_reference(self, want, avail, chunks):
        self._reference_refuses(chunks)
        return self._ec.decode_batch_reference(want, avail, chunks)


def test_flush_failure_rejects_only_the_poisoned_op(ec, way):
    """A failed batched flush DISAGGREGATES: each batchmate retries
    per-op and is served lane-for-lane exactly; only the op whose
    rows fail even under the reference sees the exception. One
    poisoned stripe must not fail its batchmates."""
    good = way.ops(ec, _rng(16), (2, 2))
    poison = np.full((1,) + good[0].shape[1:], 0xAB, dtype=np.uint8)
    flaky = _FlakyEC(ec, poison=0xAB)

    async def go():
        agg = way.cls({**_cfg(way, window_us=2000.0),
                       "osd_ec_fallback_retries": 1})
        outs = await asyncio.gather(
            way.submit(agg, flaky, good[0]),
            way.submit(agg, flaky, poison),
            way.submit(agg, flaky, good[1]),
            return_exceptions=True)
        for i, dat in ((0, good[0]), (2, good[1])):
            assert (way.rows(outs[i]) == way.direct(ec, dat)).all(), i
            if way is _Encode:
                assert outs[i][1] is None
        assert isinstance(outs[1], RuntimeError)
        d = agg.perf.dump()
        assert d.get("flush_failures", 0) == 1
        assert d.get("per_op_retries", 0) == 1   # the poison op only
        assert d.get("fallback_ops", 0) == 0     # nothing NEEDED ref
        assert agg.dump()["pending_ops"] == 0
        # the aggregator stays LIVE after a failed flush: the next
        # batch coalesces and serves normally
        out = await way.submit(agg, flaky, good[0])
        assert (way.rows(out) == way.direct(ec, good[0])).all()
        assert agg.perf.dump().get("batches", 0) == 1
    run(go())


def test_degrade_ladder_reference_serves_after_retries(ec):
    """Device encode hard-down: the op is served by the bit-exact
    host reference encoder after exactly ``osd_ec_fallback_retries``
    more device attempts — a client write never errors because the
    accelerator did; CRCs fall back to None (the caller's zlib
    path)."""
    rng = _rng(17)
    d = rng.integers(0, 256, (3, K, C), dtype=np.uint8)
    flaky = _FlakyEC(ec, fail_all=True)

    async def go():
        agg = ECAggregator({"osd_ec_agg": True,
                            "osd_ec_agg_window_us": 100.0,
                            "osd_ec_fallback_retries": 2})
        p, c = await agg.encode(flaky, d, with_crc=True)
        assert c is None
        assert (np.asarray(p) ==
                np.asarray(ec.encode_batch(d))).all()
        dmp = agg.perf.dump()
        assert dmp.get("flush_failures", 0) == 1
        assert dmp.get("per_op_retries", 0) == 2
        assert dmp.get("fallback_ops", 0) == 1
    run(go())


def test_reference_encoder_bit_exact_both_planes(ec):
    """``encode_batch_reference`` (pure numpy, no jit) equals the
    device kernel bit for bit on BOTH kernel planes: the GF(2^8)
    matmul (reed_sol_van, the module plugin) and the packet-plane
    bitmatrix XOR (liberation)."""
    rng = _rng(18)
    d = rng.integers(0, 256, (4, K, C), dtype=np.uint8)
    assert (np.asarray(ec.encode_batch_reference(d)) ==
            np.asarray(ec.encode_batch(d))).all()
    lib = ErasureCodeJax("plugin=jax k=4 m=2 technique=liberation w=7")
    dl = rng.integers(0, 256, (2, 4, 56), dtype=np.uint8)  # C = 8w
    assert (np.asarray(lib.encode_batch_reference(dl)) ==
            np.asarray(lib.encode_batch(dl))).all()


def test_fused_crc_quarantine_backoff(ec):
    """After the fused checksum+encode jit raises, flushes serve plain
    encode + host crc until an exponential-backoff deadline passes;
    the next crc flush past the deadline IS the probe, and a success
    resets the failure streak."""
    rng = _rng(19)
    d = rng.integers(0, 256, (2, K, C), dtype=np.uint8)

    class _CrcDown:
        profile = "crcdown"

        def __init__(self):
            self.fused_calls = 0
            self.ok = False

        def encode_batch(self, data):
            return ec.encode_batch(data)

        def encode_batch_with_crc(self, data):
            self.fused_calls += 1
            if not self.ok:
                raise RuntimeError("fused jit down")
            return ec.encode_batch_with_crc(data)

    plug = _CrcDown()
    agg = ECAggregator({"osd_ec_fallback_quarantine_base": 0.05,
                        "osd_ec_fallback_quarantine_max": 0.2})
    p, c = agg._run(plug, d, True)       # fused fails -> plain serves
    assert c is None and plug.fused_calls == 1
    assert (p == np.asarray(ec.encode_batch(d))).all()
    p, c = agg._run(plug, d, True)       # inside the rest window
    assert c is None and plug.fused_calls == 1    # fused NOT retried
    assert agg.perf.dump().get("crc_fallbacks", 0) == 1
    time.sleep(0.06)
    p, c = agg._run(plug, d, True)       # probe past deadline: fails
    assert plug.fused_calls == 2 and c is None
    assert agg._crc_failures == 2        # backoff doubled (0.1s)
    assert agg.perf.dump().get("crc_fallbacks", 0) == 2
    plug.ok = True
    time.sleep(0.11)
    p, c = agg._run(plug, d, True)       # probe succeeds: fused back
    assert plug.fused_calls == 3 and c is not None
    assert agg._crc_failures == 0
    assert (p == np.asarray(ec.encode_batch(d))).all()


def test_streaming_pipeline_falls_back_on_device_fault(ec):
    """An injected mid-stream jit failure loses NO batches: the
    pipeline re-encodes in-flight host copies on the non-donated
    unpipelined path and drains the rest, outputs in submission
    order — and devmon counts the fallback and the injected fault."""
    from ceph_tpu.sim import faults as F
    from ceph_tpu.utils import devmon as devmon_mod
    rng = _rng(20)
    batches = [rng.integers(0, 256, (2, K, C), dtype=np.uint8)
               for _ in range(4)]
    dm = devmon_mod.devmon()
    before = dm.perf.dump()
    inj = F.FaultInjector(seed=16)
    inj.install("stream", [F.jit_fail("ec_stream_encode", count=1)])
    devmon_mod.set_fault_injector(inj)
    try:
        pipe = StreamingEncodePipeline(ec)
        outs = pipe.encode_all([b.copy() for b in batches])
    finally:
        devmon_mod.set_fault_injector(None)
    after = dm.perf.dump()
    assert after.get("stream_fallbacks", 0) - \
        before.get("stream_fallbacks", 0) == 1
    assert after.get("faults_injected", 0) - \
        before.get("faults_injected", 0) == 1
    assert len(outs) == len(batches)
    for i, (b, o) in enumerate(zip(batches, outs)):
        assert (np.asarray(o) ==
                np.asarray(ec.encode_batch(b))).all(), i

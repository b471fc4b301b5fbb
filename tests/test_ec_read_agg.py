"""Round 19: the READ-side EC data path — the OSD decode/repair
aggregator, the bit-exact host reference decoder, the device-resident
hot-shard cache, and the one-job device scrub CRC.

ref test model: the per-op vs batched equivalence discipline of
tests/test_ec_agg.py, applied to decode. Units only (the live-cluster
acceptance rides tests/test_ec_cluster.py):

- **reference decoder** — ``decode_batch_reference`` (pure numpy, no
  jit) equals the device kernel bit for bit on BOTH kernel planes
  (GF(2^8) matmul and packet-plane bitmatrix XOR), and reconstructs
  real codewords;
- **aggregator** — what belongs to the decode direction alone (the
  policy shared with the encode direction is held once per direction
  in tests/test_ec_agg.py): ops with different erasure patterns never
  share a launch;
- **degrade ladder** — per-op device retries are bounded, the
  reference decoder serves bit-exactly as the last rung, and repeated
  failures quarantine the device decode on exponential backoff;
- **QoS honesty** — a repair decode (charge_bytes > 0) pays a
  recovery-class size-scaled grant BEFORE queueing; client degraded
  reads (charge_bytes=0) pay nothing here (already cost-tagged at
  admission);
- **residency** — DeviceShardCache LRU/budget/invalidation semantics,
  copy-on-insert immutability, and the ECBackendLite generation
  discipline (a mutator's bump makes stale entries unreachable);
- **device scrub CRC** — ``crc.device_row_crcs`` folds to
  ``zlib.crc32`` per shard, and one sweep's digests cost ONE device
  job (the O(batches)-not-O(objects) counter pin, unit leg).

One module-scoped plugin instance: every test shares its jit cache
(tier-1 runs near the wall-clock cap — compiles are the budget).
"""

import asyncio
import time
import zlib

import numpy as np
import pytest

from ceph_tpu.ec import crc as ec_crc
from ceph_tpu.ec.jax_plugin import DeviceShardCache, ErasureCodeJax
from ceph_tpu.osd.ec_aggregator import ECReadAggregator

K, M, C = 3, 2, 64
N = K + M
WANT = (0,)             # data chunk 0 lost
AVAIL = (1, 2, 3)       # survivors: data 1..2 + parity 0


@pytest.fixture(scope="module")
def ec():
    return ErasureCodeJax(
        f"plugin=jax k={K} m={M} technique=reed_sol_van")


def _rng(seed=19):
    return np.random.default_rng(seed)


def run(coro):
    return asyncio.run(coro)


def _codeword(ec, rng, b):
    """(b, N, C) real codeword batch + its data half."""
    data = rng.integers(0, 256, (b, K, C), dtype=np.uint8)
    parity = np.asarray(ec.encode_batch(data))
    return np.concatenate([data, parity], axis=1), data


def _survivors(word):
    return np.stack([word[:, i, :] for i in AVAIL], axis=1)


# -- the reference decoder -------------------------------------------------

def test_reference_decoder_bit_exact_both_planes(ec):
    """``decode_batch_reference`` equals the device decode bit for bit
    on both kernel planes, and reconstructs real codewords."""
    rng = _rng(1)
    word, data = _codeword(ec, rng, 4)
    chunks = _survivors(word)
    ref = np.asarray(ec.decode_batch_reference(WANT, AVAIL, chunks))
    dev = np.asarray(ec.decode_batch(WANT, AVAIL, chunks))
    assert (ref == dev).all()
    assert (ref[:, 0, :] == data[:, 0, :]).all()   # actual recovery
    # packet-plane bitmatrix (liberation, w=7): same contract
    lib = ErasureCodeJax("plugin=jax k=4 m=2 technique=liberation w=7")
    dl = rng.integers(0, 256, (2, 4, 56), dtype=np.uint8)   # C = 8w
    pl = np.asarray(lib.encode_batch(dl))
    wl = np.concatenate([dl, pl], axis=1)
    av = (1, 2, 3, 4)
    ch = np.stack([wl[:, i, :] for i in av], axis=1)
    assert (np.asarray(lib.decode_batch_reference((0,), av, ch)) ==
            np.asarray(lib.decode_batch((0,), av, ch))).all()


# -- the aggregator --------------------------------------------------------

def test_read_aggregator_groups_by_erasure_pattern(ec):
    """Ops with DIFFERENT (avail, want) never share a launch — the
    group key is the decode-kernel cache key."""
    rng = _rng(3)
    word, _ = _codeword(ec, rng, 2)
    a = _survivors(word)
    b = np.stack([word[:, i, :] for i in (0, 2, 4)], axis=1)

    async def go():
        agg = ECReadAggregator({"osd_ec_read_agg": True,
                                "osd_ec_read_agg_window_us": 2000.0})
        oa, ob = await asyncio.gather(
            agg.decode(ec, WANT, AVAIL, a),
            agg.decode(ec, (1,), (0, 2, 4), b))
        assert agg.dump()["batches"] == 2    # distinct groups
        assert (np.asarray(oa) == np.asarray(
            ec.decode_batch(WANT, AVAIL, a))).all()
        assert (np.asarray(ob) == np.asarray(
            ec.decode_batch((1,), (0, 2, 4), b))).all()
    run(go())


# -- the degrade ladder ----------------------------------------------------

class _FlakyDecodeEC:
    """Delegates to the module plugin but fails on command: device
    decodes raise while a ``poison`` chunk batch rides along (or
    always, with ``fail_all``), and the reference decoder refuses the
    poison batch itself — the worst case the ladder must isolate."""

    profile = "flaky"

    def __init__(self, ec, poison=None, fail_all=False):
        self._ec = ec
        self._poison = poison
        self.fail_all = fail_all
        self.device_calls = 0

    def _poisoned(self, chunks):
        return self._poison is not None and \
            bool((chunks == self._poison).all(axis=(1, 2)).any())

    def decode_batch(self, want, avail, chunks):
        self.device_calls += 1
        if self.fail_all or self._poisoned(np.asarray(chunks)):
            raise RuntimeError("injected device failure")
        return self._ec.decode_batch(want, avail, chunks)

    def decode_batch_reference(self, want, avail, chunks):
        if self._poisoned(np.asarray(chunks)):
            raise RuntimeError("reference refuses the poison batch")
        return self._ec.decode_batch_reference(want, avail, chunks)


def test_read_degrade_ladder_reference_and_quarantine(ec):
    """Device decode hard-down: the op is served by the bit-exact
    reference decoder after bounded retries; repeated failures
    quarantine the device (later ops go straight to the reference,
    zero device calls), and the quarantine expires on backoff."""
    rng = _rng(10)
    d = _survivors(_codeword(ec, rng, 3)[0])
    # this shape's first decode compiles: outside the 50 ms quarantine
    ec.decode_batch(WANT, AVAIL, d)
    flaky = _FlakyDecodeEC(ec, fail_all=True)

    async def go():
        agg = ECReadAggregator({
            "osd_ec_read_agg": False,    # bypass: per-op ladder
            "osd_ec_fallback_retries": 1,
            "osd_ec_fallback_quarantine_base": 0.05,
            "osd_ec_fallback_quarantine_max": 0.2})
        out = await agg.decode(flaky, WANT, AVAIL, d)
        assert (np.asarray(out) == np.asarray(
            ec.decode_batch(WANT, AVAIL, d))).all()
        dmp = agg.perf.dump()
        assert dmp.get("per_op_retries", 0) == 1
        assert dmp.get("fallback_ops", 0) == 1
        calls = flaky.device_calls           # initial try + 1 retry
        assert calls == 2
        # quarantined: the next op never touches the device
        out = await agg.decode(flaky, WANT, AVAIL, d)
        assert (np.asarray(out) == np.asarray(
            ec.decode_batch(WANT, AVAIL, d))).all()
        assert flaky.device_calls == calls
        assert agg.perf.dump().get("quarantined_ops", 0) == 1
        # past the backoff deadline the device is probed again
        time.sleep(0.06)
        await agg.decode(flaky, WANT, AVAIL, d)
        assert flaky.device_calls > calls
        assert agg._dev_failures == 2        # backoff doubled
    run(go())


# -- QoS honesty -----------------------------------------------------------

class _StubScheduler:
    def __init__(self):
        self.grants = []

    async def grant(self, op_class, key=None, cost=1.0):
        self.grants.append((op_class, float(cost)))


def test_repair_decode_charges_recovery_grant(ec):
    """charge_bytes > 0 (a rebuild/backfill decode) pays a
    recovery-class grant at the bytes/osd_qos_cost_per_io_bytes
    divisor BEFORE queueing; charge_bytes=0 (a client degraded read,
    already cost-tagged at admission) pays nothing here."""
    rng = _rng(11)
    d = _survivors(_codeword(ec, rng, 2)[0])
    sched = _StubScheduler()

    async def go():
        agg = ECReadAggregator(
            {"osd_ec_read_agg": False,
             "osd_qos_cost_per_io_bytes": 4096},
            scheduler=sched)
        await agg.decode(ec, WANT, AVAIL, d,
                         charge_bytes=int(d.nbytes))
        assert len(sched.grants) == 1
        op_class, cost = sched.grants[0]
        assert op_class == "recovery"
        assert cost == pytest.approx(max(1.0, d.nbytes / 4096))
        assert agg.perf.dump().get("qos_grants", 0) == 1
        # client degraded read: no double charge
        await agg.decode(ec, WANT, AVAIL, d, charge_bytes=0)
        assert len(sched.grants) == 1
    run(go())


# -- hot-shard residency ---------------------------------------------------

def test_device_shard_cache_lru_budget_invalidate():
    """LRU order, byte budget, oversized reject, prefix invalidation,
    budget-0 disable, and copy-on-insert immutability."""
    ent = np.zeros((2, 3, C), dtype=np.uint8)     # 384 bytes each
    cfg = {"osd_ec_resident_bytes": 3 * ent.nbytes}
    cache = DeviceShardCache(cfg)
    for i in range(3):
        cache.put(("pg1", f"o{i}", 0), np.full_like(ent, i))
    assert cache.get(("pg1", "o0", 0)) is not None   # o0 -> MRU
    cache.put(("pg1", "o3", 0), np.full_like(ent, 3))
    assert cache.get(("pg1", "o1", 0)) is None       # LRU evicted
    assert cache.get(("pg1", "o0", 0)) is not None
    d = cache.perf.dump()
    assert d.get("evictions", 0) == 1
    # oversized single entry: rejected, cache unchanged
    cache.put(("pg1", "big", 0),
              np.zeros(4 * ent.nbytes, dtype=np.uint8))
    assert cache.perf.dump().get("rejected", 0) == 1
    # prefix invalidation drops only the matching object's entries
    cache.put(("pg2", "oX", 0), ent)
    n = cache.invalidate("pg1")
    assert n >= 2 and cache.get(("pg2", "oX", 0)) is not None
    assert cache.get(("pg1", "o0", 0)) is None
    # copy-on-insert: mutating the source after put can't corrupt
    src = np.full_like(ent, 7)
    cache.put(("pg2", "oY", 0), src)
    src[:] = 0
    assert (np.asarray(cache.get(("pg2", "oY", 0))) == 7).all()
    # budget 0 disables lookups AND inserts
    off = DeviceShardCache({"osd_ec_resident_bytes": 0})
    off.put(("k",), ent)
    assert off.get(("k",)) is None and not off.enabled()


def test_ec_backend_residency_generation_discipline(ec):
    """ECBackendLite with residency on: repeated reads hit the cache;
    every mutator (write/lose_shard/recover) bumps the generation so
    RMW merges never see stale device bytes — readback stays exact."""
    from ceph_tpu.osd.ec_backend import ECBackendLite
    be = ECBackendLite(ec, chunk_size=C,
                       config={"osd_ec_resident_bytes": 1 << 20})
    assert be.resident is not None
    rng = _rng(12)
    payload = rng.integers(0, 256, 2 * K * C, dtype=np.uint8).tobytes()
    be.write("obj", 0, payload)
    assert be.read("obj", 0, len(payload)) == payload    # miss + pin
    h0 = be.resident.perf.dump().get("hits", 0)
    assert be.read("obj", 0, len(payload)) == payload    # device hit
    assert be.resident.perf.dump().get("hits", 0) > h0
    # a mutator bumps the generation: the stale pin is unreachable
    # and the RMW merge never sees old device bytes
    be.write("obj", 10, b"\xDD" * 40)
    want = bytearray(payload)
    want[10:50] = b"\xDD" * 40
    assert be.read("obj", 0, len(payload)) == bytes(want)
    assert be.read("obj", 0, len(payload)) == bytes(want)  # fresh hit
    # recovery after shard loss still reads back exactly (gen bumped)
    be.lose_shard(0, "obj")
    assert be.recover("obj") == {0}
    assert be.read("obj", 0, len(payload)) == bytes(want)


# -- one-job device scrub CRC ----------------------------------------------

def test_device_row_crcs_fold_to_zlib():
    """(R, C) device row CRCs fold per shard to zlib.crc32 exactly —
    the byte-equality the one-job scrub stands on."""
    rng = _rng(13)
    rows = rng.integers(0, 256, (12, C), dtype=np.uint8)
    rcs = ec_crc.device_row_crcs(rows)
    assert rcs.shape == (12,) and rcs.dtype == np.uint32
    assert int(ec_crc.shard_crc32(rcs, C)) == zlib.crc32(rows.tobytes())
    # multi-shard fold (the _deep_ec_check layout: (count, m).T)
    per = rcs.reshape(4, 3).transpose()           # 3 shards x 4 rows
    got = [int(x) for x in ec_crc.shard_crc32(per, C)]
    want = [zlib.crc32(rows.reshape(4, 3, C)[:, s, :].tobytes())
            for s in range(3)]
    assert got == want


def test_scrub_sweep_digests_are_one_device_job():
    """The build_scrub_map sweep digests every C-divisible object in
    ONE device CRC launch (counter-pinned); ragged/empty payloads fall
    back to host zlib, byte-identically."""
    from ceph_tpu.osd.scrub import SCRUB_PERF, _device_digests

    class _Pool:
        def is_erasure(self):
            return True

    class _Sinfo:
        chunk_size = C

    class _PG:
        pool = _Pool()
        sinfo = _Sinfo()
        pgid = "9.0"

    rng = _rng(14)
    loaded = [(f"o{i}", rng.integers(0, 256, (i + 1) * C,
                                     dtype=np.uint8).tobytes(),
               {}, {}) for i in range(6)]
    loaded.append(("ragged", b"\x01" * (C + 3), {}, {}))
    loaded.append(("empty", b"", {}, {}))
    before = SCRUB_PERF.dump()
    digests = _device_digests(_PG(), loaded)
    after = SCRUB_PERF.dump()
    assert after.get("device_crc_jobs", 0) - \
        before.get("device_crc_jobs", 0) == 1      # ONE job, 6 objects
    assert after.get("device_crc_rows", 0) - \
        before.get("device_crc_rows", 0) == sum(range(1, 7))
    assert set(digests) == {f"o{i}" for i in range(6)}
    for oid, data, _a, _o in loaded[:6]:
        assert digests[oid] == zlib.crc32(data), oid
    # replicated PGs never touch the device path

    class _RepPool:
        def is_erasure(self):
            return False

    class _RepPG:
        pool = _RepPool()
        sinfo = None
        pgid = "9.1"

    assert _device_digests(_RepPG(), loaded) == {}

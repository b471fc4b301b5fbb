"""The primary's side of an EC write: ``ECPG._submit_ec_write`` builds
all k+m sub-write payloads and ``_hcrc`` stamps in one pass
(``ECPG._shard_payloads``: one lane-major copy a block, one CRC fold for
all shards).

A bare harness: one real ``ECPG`` over a ``MemStore`` behind a stub OSD
that holds the two aggregators a daemon would (an ``ECPG`` has no
launch of its own) and the fan-out captured instead of sent. Every sent
position is held to the idiom the pass replaced:
``data_chunks[:, pos, :].tobytes()`` (parity alike) and ``zlib.crc32``
of those bytes.
"""

import asyncio
import types
import zlib

import numpy as np
import pytest

from ceph_tpu.ec import crc as ec_crc
from ceph_tpu.os_.objectstore import MemStore
from ceph_tpu.osd.ec_aggregator import ECAggregator, ECReadAggregator
from ceph_tpu.osd.ec_pg import ECPG
from ceph_tpu.osd.types import pg_t

# (k, m) -> stripe_unit: the served cells' own shape, and a second code
PROFILES = {(8, 3): 4096, (4, 2): 1024}


class _StubOSD:
    """What ``_submit_ec_write`` asks of its daemon, and no more."""

    whoami = 0
    tracer = None

    def __init__(self):
        self.store = MemStore()
        self.config = {}
        self.ec_agg = ECAggregator(self.config)
        self.ec_read_agg = ECReadAggregator(self.config)
        self.down: set[int] = set()
        self._tid = 0

    def osd_is_up(self, osd_id: int) -> bool:
        return osd_id not in self.down

    def next_tid(self) -> int:
        self._tid += 1
        return self._tid


@pytest.fixture(scope="module")
def pgs():
    """One PG a profile, shared by the cases (one jit cache each)."""
    made = {}
    for seed, ((k, m), unit) in enumerate(PROFILES.items()):
        pool = types.SimpleNamespace(
            min_size=k, extra={"profile": {
                "plugin": "jax", "technique": "reed_sol_van",
                "k": k, "m": m, "stripe_unit": unit}})
        made[k, m] = ECPG(_StubOSD(), pool, pg_t(1, seed))
    return made


def _arm(pg: ECPG, acting, with_crc: bool = True) -> dict:
    """Point ``pg`` at ``acting``, capture what it would fan out."""
    pg.acting = list(acting)
    pg.osd.down.clear()
    pg.backfill_targets.clear()
    sent = {}

    async def fan_out(tid, per_osd):
        sent.update(per_osd)
        return len(per_osd)

    async def no_row_crcs(data_chunks, with_crc=False, span=None):
        return np.asarray(pg.ec.encode_batch(data_chunks)), None

    pg._fan_out_subops = fan_out
    pg.__dict__.pop("_agg_encode", None)
    if not with_crc:
        pg._agg_encode = no_row_crcs
    return sent


def _write(pg: ECPG, oid: str, payload: bytes, whole: bool) -> int:
    edits, write_full = ([], payload) if whole else ([(0, payload)], None)
    return asyncio.run(pg._submit_ec_write(
        oid, edits, write_full, None, False, {}, {}))


def _expected(pg: ECPG, payload: bytes, count: int):
    """[(shard bytes, zlib crc)] by position, the old way: a strided
    ``tobytes`` of each position of the zero-padded stripes."""
    buf = np.zeros(count * pg.sinfo.stripe_width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = buf.reshape(count, pg.k, pg.sinfo.chunk_size)
    parity = np.asarray(pg.ec.encode_batch(data))
    shards = [data[:, p, :].tobytes() for p in range(pg.k)] + \
        [parity[:, p, :].tobytes() for p in range(pg.m)]
    return [(s, zlib.crc32(s).to_bytes(4, "little")) for s in shards]


@pytest.mark.parametrize("with_crc", [True, False],
                         ids=["row_crcs", "no_row_crcs"])
@pytest.mark.parametrize("whole", [True, False],
                         ids=["whole", "partial"])
@pytest.mark.parametrize("count", [1, 3, 128])
@pytest.mark.parametrize("km", list(PROFILES), ids=lambda km: "%d+%d" % km)
def test_sub_writes_carry_the_old_bytes_and_stamps(pgs, km, count,
                                                   whole, with_crc):
    pg = pgs[km]
    n = pg.k + pg.m
    sent = _arm(pg, range(n), with_crc)
    # the last stripe is short: the pad bytes are part of every shard
    size = count * pg.sinfo.stripe_width - 5
    payload = np.random.default_rng(count * n).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    oid = f"o-{count}-{whole}-{with_crc}"
    assert _write(pg, oid, payload, whole) == 0
    assert sorted(sent) == list(range(n))
    for pos, (shard, crc) in enumerate(_expected(pg, payload, count)):
        msg = sent[pos]
        assert type(msg.data) is bytes and msg.data == shard, pos
        assert msg.attrs["_hcrc"] == (crc if whole else b""), pos
        assert msg.attrs["_pos"] == ECPG._pos_attr(pos)
        assert msg.attrs["_size"] == size.to_bytes(8, "little")
        assert (msg.first_stripe, msg.truncate_stripes) == (0, count)


def test_a_hole_gets_no_message(pgs):
    """No OSD at a position, an OSD down, a backfill target above its
    watermark: their payload rows are computed in the bulk pass and
    never sent; every other position's message is its own."""
    pg = pgs[8, 3]
    acting = [10, 11, -1, 13, 14, 15, 16, 17, 18, 19, 20]
    sent = _arm(pg, acting)
    pg.osd.down.add(15)
    pg.backfill_targets[19] = ""            # holds nothing yet
    payload = bytes(range(256)) * (3 * pg.sinfo.stripe_width // 256)
    assert _write(pg, "holey", payload, True) == 0
    assert sorted(sent) == [10, 11, 13, 14, 16, 17, 18, 20]
    expected = _expected(pg, payload, 3)
    for osd_id, msg in sent.items():
        pos = acting.index(osd_id)
        assert (msg.data, msg.attrs["_hcrc"]) == expected[pos], pos
        assert msg.attrs["_pos"] == ECPG._pos_attr(pos)


def test_fewer_than_k_committed_fails_the_write(pgs):
    pg = pgs[4, 2]
    _arm(pg, range(6))

    async def three_commit(tid, per_osd):
        return pg.k - 1

    pg._fan_out_subops = three_commit
    assert _write(pg, "lost", b"x" * 100, True) == -5


@pytest.mark.parametrize("whole,with_crc,folds", [
    (True, True, 1), (True, False, 0), (False, True, 0)],
    ids=["whole", "whole-no_row_crcs", "partial"])
def test_one_fold_a_whole_object_write(pgs, monkeypatch, whole,
                                       with_crc, folds):
    """``combine_row_crcs`` is entered once for all k+m shards of a
    whole-object write, not once a position; a partial overwrite and a
    write without device row CRCs never enter it."""
    pg = pgs[8, 3]
    _arm(pg, range(11), with_crc)
    calls = []
    fold = ec_crc.combine_row_crcs

    def counted(row_crcs, chunk_size):
        calls.append(np.shape(row_crcs))
        return fold(row_crcs, chunk_size)

    monkeypatch.setattr(ec_crc, "combine_row_crcs", counted)
    payload = b"\x5a" * (3 * pg.sinfo.stripe_width)
    assert _write(pg, f"fold-{whole}-{with_crc}", payload, whole) == 0
    assert calls == [(11, 3)] * folds


@pytest.mark.parametrize("count", [1, 2, 3, 5, 16, 100, 128])
def test_fold_of_many_shards_equals_zlib(count):
    """The pairwise fold, whatever the count (a power of two or not),
    for a batch of shards at once."""
    C = 64
    rows = np.random.default_rng(count).integers(
        0, 256, (4, count, C), dtype=np.uint8)
    row_crcs = np.array([[ec_crc.raw_crc(r.tobytes()) for r in shard]
                         for shard in rows], dtype=np.uint32)
    shards = [s.tobytes() for s in rows]
    want = [zlib.crc32(s).to_bytes(4, "little") for s in shards]
    assert ec_crc.hcrc_attrs(shards, row_crcs, C) == want
    assert ec_crc.hcrc_attrs(shards) == want
    assert [ec_crc.hcrc_attr(s, rc, C)
            for s, rc in zip(shards, row_crcs)] == want

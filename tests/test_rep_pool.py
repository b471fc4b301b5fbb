"""A replicated pool through the normal path, against the plain
reference ``benchmark/reference/rep_ref.py`` (which imports nothing of
the program): the stored copies, their holders and the read-backs of
seeded payloads; the op-path spans and counters of the replicated
backend; and the ack that waits for every replica's commit.
"""

import asyncio
import json
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from drivers import rados_bench, rados_bench_rep          # noqa: E402
from reference import rep_ref                             # noqa: E402

from ceph_tpu.cluster.vstart import Cluster               # noqa: E402
from ceph_tpu.sim import faults as F                      # noqa: E402
from ceph_tpu.utils import tracing                        # noqa: E402

REP_SECTIONS = {"osd.rep_prepare", "osd.rep_fanout", "osd.rep_apply"}


def run(coro):
    return asyncio.run(coro)


async def _rep_cluster(n_osds=4, pg_num=8, **kw):
    c = await Cluster(n_mons=1, n_osds=n_osds, **kw).start()
    await c.client.pool_create("rep", pg_num=pg_num, size=3, min_size=2)
    await c.wait_for_clean(timeout=120)
    return c, await c.client.open_ioctx("rep")


def _reference_pool(c, io) -> rep_ref.Pool:
    om = c.leader().osdmon.osdmap
    return rep_ref.Pool(*rados_bench_rep.describe(om, io.pool_id))


def _holders(c, oid: str) -> dict:
    """{osd: (collection, bytes)} over every store that has the object."""
    return {o.whoami: (cid, bytes(coll[oid].data))
            for o in c.osds for cid, coll in o.store.colls.items()
            if oid in coll}


@pytest.mark.parametrize("size,pg_num", [
    (1, 8), (4096, 12), (65537, 8), (1 << 20, 32)],
    ids=["1B-pg8", "4KiB-pg12", "64KiB+1-pg8", "1MiB-pg32"])
def test_replicas_holders_and_reads_are_the_references(size, pg_num):
    payloads = rados_bench.Payloads(seed=2 ** 31 + size, size=size, bases=2)
    names = [f"benchmark_data_{i}" for i in range(10)]

    async def go():
        c, io = await _rep_cluster(pg_num=pg_num)
        try:
            for i, oid in enumerate(names):
                await io.write_full(oid, payloads.get(i))
            ref = _reference_pool(c, io)
            pgs = set()
            for i, oid in enumerate(names):
                want, acting = payloads.get(i), ref.acting(oid)
                assert len(acting) == len(set(acting)) == 3
                held = _holders(c, oid)
                assert sorted(held) == sorted(acting), (oid, held.keys())
                for copy, (cid, got) in zip(
                        rep_ref.replicas(want, 3),
                        (held[o] for o in acting)):
                    assert cid == ref.pgid(oid) and got == copy
                ret, _, out = await c.client.mon_command(
                    {"prefix": "osd map", "pool": "rep", "object": oid})
                info = json.loads(out)
                assert ret == 0 and info["pgid"] == ref.pgid(oid)
                assert info["acting"] == acting      # primary first
                assert await io.read(oid) == want
                pgs.add(ref.pg_of(oid))
            assert len(pgs) > 1 and max(pgs) < pg_num
        finally:
            await c.stop()
    run(go())


def _captured() -> list[dict]:
    return [tracing.record_dict(r) for r in tracing.captured()]


def _rep_totals(c) -> tuple[int, int]:
    dumps = [o.perf.dump() for o in c.osds]
    return (sum(d["rep_ops"] for d in dumps),
            sum(d["rep_fanout_bytes"] for d in dumps))


def test_rep_spans_and_counters_for_a_replicated_write_only(tmp_path):
    """Under a profiler session a replicated write_full yields the three
    sections, the ``rep_subop_wait`` interval and both counters; an EC
    write_full yields none of them."""
    import jax
    payload = bytes(range(256)) * 192            # 48 KiB

    async def go():
        c, io = await _rep_cluster(
            n_osds=6, config={"osd_ec_resident_bytes": 8 << 20})
        try:
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "osd erasure-code-profile set", "name": "p32",
                 "profile": ["k=3", "m=2", "plugin=jax",
                             "technique=reed_sol_van", "stripe_unit=4096"]})
            assert ret == 0, rs
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "osd pool create", "pool": "ec", "pg_num": 4,
                 "pool_type": "erasure", "erasure_code_profile": "p32"})
            assert ret == 0, rs
            await c.wait_for_clean(timeout=120)
            ec = await c.client.open_ioctx("ec")
            await io.write_full("warm", payload)      # connections up,
            await ec.write_full("warm", payload)      # programs warm
            base = _rep_totals(c)
            jax.profiler.start_trace(str(tmp_path))
            try:
                await io.write_full("traced", payload)
                after_rep = _rep_totals(c)
                await ec.write_full("traced", payload)
            finally:
                jax.profiler.stop_trace()
            return base, after_rep, _rep_totals(c)
        finally:
            await c.stop()
    base, after_rep, after_ec = run(go())
    assert after_rep == (base[0] + 1, base[1] + 2 * len(payload))
    assert after_ec == after_rep                  # the EC write: nothing
    recs = _captured()
    assert tracing.capture_info()["dropped"] == 0
    rep_root, ec_root = [r for r in recs if r["name"] == "client_op"]
    mine = [r for r in recs if r["trace_id"] == rep_root["trace_id"]]
    kinds = {r["name"]: r["kind"] for r in mine}
    assert REP_SECTIONS <= set(kinds), REP_SECTIONS - set(kinds)
    assert all(kinds[n] == "section" for n in REP_SECTIONS)
    assert kinds["rep_subop_wait"] == "interval"
    by_name = {}
    for r in mine:
        by_name.setdefault(r["name"], []).append(r)
    (wait,) = by_name["rep_subop_wait"]
    assert wait["tags"]["replicas"] and len(by_name["osd.rep_apply"]) == 2
    # each replica's apply, and its store commit, lie inside the wait
    for r in by_name["osd.rep_apply"]:
        assert wait["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= wait["t1_ns"]
        assert r["service"] != wait["service"]
    assert len(by_name["objectstore_commit"]) == 3
    (prep,), (fan,) = by_name["osd.rep_prepare"], by_name["osd.rep_fanout"]
    assert prep["t1_ns"] <= fan["t0_ns"] <= wait["t1_ns"]
    assert prep["service"] == fan["service"] == wait["service"]
    theirs = {r["name"] for r in recs if r["trace_id"] == ec_root["trace_id"]}
    assert "osd.ec_prepare" in theirs
    assert not theirs & (REP_SECTIONS | {"rep_subop_wait"})


def test_the_ack_waits_for_the_third_commit():
    """The primary's MOSDRepOp to one replica is held back by a fault
    rule: while it is held the replica's store lacks the object and
    ``write_full`` has not returned; when it returns all three stores
    hold it."""
    hold = 0.8
    payload = b"\xa5" * 70000

    async def go():
        c, io = await _rep_cluster()
        try:
            await io.write_full("warm", payload)
            ref = _reference_pool(c, io)
            primary, _second, third = ref.acting("held")
            inj = F.FaultInjector(seed=1)
            c.install_faults(inj)
            inj.install("hold", [F.delay(f"osd.{primary}", f"osd.{third}",
                                         hold)])
            t0 = time.monotonic()
            write = asyncio.ensure_future(io.write_full("held", payload))
            await asyncio.sleep(hold / 2)
            midway = _holders(c, "held")
            assert not write.done()
            assert third not in midway and primary in midway
            await write
            took = time.monotonic() - t0
            inj.clear_all()
            assert took >= hold
            at_ack = _holders(c, "held")
            assert sorted(at_ack) == sorted(ref.acting("held"))
            assert all(got == payload for _cid, got in at_ack.values())
        finally:
            await c.stop()
    run(go())


@pytest.mark.parametrize("name", [
    # lengths around ceph_str_hash_rjenkins' 12-byte block and its tail
    "a", "benchmark_da", "benchmark_data_17", "x" * 23, "y" * 24,
    "rbd_data.10226b8b4567.00000000000003ff"])
def test_name_hash_is_the_programs(name):
    from ceph_tpu.osd.str_hash import str_hash_rjenkins
    assert rep_ref.str_hash_rjenkins(name.encode()) == \
        str_hash_rjenkins(name.encode())

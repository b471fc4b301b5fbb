"""``reference/rep_ref.py`` against cases worked by hand, and against
the program's own placement (they share no code)."""

import numpy as np
import pytest

from reference import crush_ref, rep_ref


def hosts_map(n: int, weights=None) -> dict:
    """vstart's tree: a root over n hosts of one OSD each, the default
    replicated rule."""
    return {
        "max_devices": n,
        "buckets": [{"id": -1, "type": 10, "alg": "straw2",
                     "items": [-2 - i for i in range(n)],
                     "weights": [0x10000] * n}] +
                   [{"id": -2 - i, "type": 1, "alg": "straw2",
                     "items": [i], "weights": [0x10000]}
                    for i in range(n)],
        "rule": [["take", -1, 0], ["chooseleaf_firstn", 0, 1],
                 ["emit", 0, 0]],
        "tunables": {},
    }


POOL = {"id": 1, "pg_num": 32, "pgp_num": 32, "size": 3, "hashpspool": True}


def test_the_documented_hash_of_foo():
    # `ceph osd map <pool> foo` prints pg <pool>.7fc1f406 in upstream's docs
    assert rep_ref.str_hash_rjenkins(b"foo") == 0x7FC1F406


@pytest.mark.parametrize("x,b,want", [
    (13, 12, 5),        # 13 & 15 = 13 is past 12: fold onto 13 & 7
    (11, 12, 11), (0x7FC1F406, 8, 6), (0x7FC1F406, 32, 6), (31, 32, 31),
    (5, 1, 0)])
def test_stable_mod_by_hand(x, b, want):
    assert rep_ref.stable_mod(x, b, rep_ref.mask_of(b)) == want


def test_masks():
    assert [rep_ref.mask_of(n) for n in (1, 2, 8, 12, 32, 33)] == \
        [0, 1, 7, 15, 31, 63]


def test_three_hosts_hold_every_object_once_each():
    pool = rep_ref.Pool(POOL, hosts_map(3))
    for i in range(40):
        acting = pool.acting(f"benchmark_data_{i}")
        assert sorted(acting) == [0, 1, 2]
    assert pool.pgid("foo") == "1.6"
    assert len({tuple(pool.acting_of_pg(pg)) for pg in range(32)}) > 1


def test_an_out_osd_holds_nothing_and_copies_are_whole():
    weights = [0x10000] * 12
    weights[4] = 0
    pool = rep_ref.Pool(POOL, hosts_map(12), weights)
    everyone = rep_ref.Pool(POOL, hosts_map(12))
    moved = 0
    for pg in range(32):
        acting = pool.acting_of_pg(pg)
        assert 4 not in acting and len(set(acting)) == 3
        moved += acting != everyone.acting_of_pg(pg)
    assert 0 < moved < 32
    assert rep_ref.replicas(b"abc", 3) == [b"abc"] * 3


def test_a_rule_or_bucket_outside_the_reference_is_refused():
    desc = hosts_map(3)
    desc["rule"][1][0] = "chooseleaf_indep"
    with pytest.raises(ValueError):
        rep_ref.crush_map(desc)
    desc = hosts_map(3)
    desc["buckets"][0]["alg"] = "uniform"
    with pytest.raises(ValueError):
        rep_ref.crush_map(desc)


@pytest.mark.parametrize("pg_num", [8, 12, 32])
def test_placement_is_the_programs(pg_num):
    """The program's OSDMap over twelve one-OSD hosts, described to the
    reference by the driver: PG and acting set of 200 names agree."""
    from ceph_tpu.crush import builder
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import ObjectLocator, PGPool
    from drivers import rados_bench_rep
    crush, root = builder.build_hierarchy(12, 1)
    builder.add_simple_rule(crush, root, builder.TYPE_HOST,
                            name="replicated_rule")
    om = OSDMap(crush)
    om.pools[1] = PGPool(id=1, pg_num=pg_num, size=3, crush_rule=0,
                         name="rep")
    pool_desc, crush_desc, weights = rados_bench_rep.describe(om, 1)
    ref = rep_ref.Pool(pool_desc, crush_desc, weights)
    for i in range(200):
        oid = f"benchmark_data_{i}"
        pg = om.object_locator_to_pg(oid, ObjectLocator(pool=1))
        seed = int(om.pools[1].raw_pg_to_pg(np.asarray([pg.seed]))[0])
        assert ref.pg_of(oid) == seed
        acting, _prim = om.pg_to_acting_primary(1, seed)
        assert ref.acting(oid) == acting

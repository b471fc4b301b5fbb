"""CRUSH mapper tests.

Two tiers, mirroring the reference's crush test strategy
(ref: src/test/crush/TestCrushWrapper.cc + crushtool --test fixtures):
1. semantic assertions on the scalar spec (distinct failure domains,
   weight proportionality, reweight-out behavior);
2. exact cross-validation of the vectorized JAX mapper against the scalar
   spec over a matrix of map shapes, algorithms and rules, including
   randomized maps.
"""

import numpy as np
import pytest

from ceph_tpu.crush import builder, mapper_ref
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.types import (
    ALG_LIST, ALG_STRAW2, ALG_UNIFORM, ITEM_NONE, WEIGHT_ONE,
    OP_CHOOSE_FIRSTN, OP_CHOOSE_INDEP, OP_CHOOSELEAF_FIRSTN, RuleStep,
    Tunables,
)

N_X = 256  # xs per config; full sweeps ran during bring-up


def assert_match(m, rid, numrep, xs=None, weights=None):
    xs = xs if xs is not None else np.arange(N_X, dtype=np.uint32)
    mapper = Mapper(m, np.asarray(weights, dtype=np.int64)
                    if weights is not None else None)
    got = np.asarray(mapper.map_pgs(rid, xs, numrep))
    wl = list(weights) if weights is not None else None
    for i, x in enumerate(xs):
        ref = mapper_ref.do_rule(m, rid, int(x), numrep, weight=wl)
        ref = ref + [ITEM_NONE] * (numrep - len(ref))
        assert list(got[i]) == ref, (int(x), list(got[i]), ref)


class TestScalarSemantics:
    def test_firstn_distinct_and_complete(self):
        m, root = builder.build_flat(10)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        for x in range(300):
            out = mapper_ref.do_rule(m, rid, x, 3)
            assert len(out) == 3 and len(set(out)) == 3

    def test_chooseleaf_distinct_hosts(self):
        m, root = builder.build_hierarchy(6, 4)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        for x in range(300):
            out = mapper_ref.do_rule(m, rid, x, 3)
            hosts = {o // 4 for o in out}
            assert len(hosts) == 3

    def test_indep_positions_and_domains(self):
        m, root = builder.build_hierarchy(8, 2)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
        for x in range(200):
            out = mapper_ref.do_rule(m, rid, x, 6)
            assert len(out) == 6
            real = [o for o in out if o != ITEM_NONE]
            assert len({o // 2 for o in real}) == len(real)

    def test_reweight_zero_excludes(self):
        m, root = builder.build_flat(5)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        w = [0x10000, 0, 0x10000, 0x10000, 0x10000]
        for x in range(200):
            assert 1 not in mapper_ref.do_rule(m, rid, x, 3, weight=w)

    def test_mapping_stability_under_weight_change(self):
        """CRUSH's core promise: adjusting one item's weight only moves
        data to/from that item (statistically)."""
        m1, root1 = builder.build_flat(8)
        r1 = builder.add_simple_rule(m1, root1, builder.TYPE_OSD)
        w2 = [WEIGHT_ONE] * 8
        w2[3] = WEIGHT_ONE // 2
        m2, root2 = builder.build_flat(8, weights=w2)
        r2 = builder.add_simple_rule(m2, root2, builder.TYPE_OSD)
        moved_not_involving_3 = 0
        total_moved = 0
        for x in range(500):
            a = mapper_ref.do_rule(m1, r1, x, 1)[0]
            b = mapper_ref.do_rule(m2, r2, x, 1)[0]
            if a != b:
                total_moved += 1
                if a != 3 and b != 3:
                    moved_not_involving_3 += 1
        assert total_moved > 0
        assert moved_not_involving_3 == 0

    def test_legacy_tunables_run(self):
        """The scalar spec also executes legacy tunables (retries>0)."""
        m, root = builder.build_hierarchy(4, 3, tunables=Tunables.legacy())
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        out = mapper_ref.do_rule(m, rid, 42, 3)
        assert len(out) == 3


class TestJaxMatchesScalar:
    def test_flat_straw2(self):
        m, root = builder.build_flat(10)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        assert_match(m, rid, 3)

    def test_flat_list(self):
        m, root = builder.build_flat(7, alg=ALG_LIST)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        assert_match(m, rid, 3)

    def test_hierarchy_chooseleaf_firstn(self):
        m, root = builder.build_hierarchy(6, 4)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        assert_match(m, rid, 3)

    def test_hierarchy_chooseleaf_indep(self):
        m, root = builder.build_hierarchy(6, 4)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
        assert_match(m, rid, 5)

    @pytest.mark.slow
    def test_uniform_buckets(self):
        m, root = builder.build_hierarchy(5, 4, alg=ALG_UNIFORM)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        assert_match(m, rid, 3)
        rid2 = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
        assert_match(m, rid2, 4)

    @pytest.mark.slow
    def test_three_level_multistep(self):
        m, root = builder.build_hierarchy(8, 2, n_racks=4)
        rid = builder.add_multistep_rule(m, root, [
            RuleStep(OP_CHOOSE_FIRSTN, 2, builder.TYPE_RACK),
            RuleStep(OP_CHOOSELEAF_FIRSTN, 2, builder.TYPE_HOST)])
        assert_match(m, rid, 4)

    @pytest.mark.slow
    def test_choose_indep_direct_osd(self):
        m, root = builder.build_hierarchy(6, 3)
        rid = builder.add_multistep_rule(
            m, root, [RuleStep(OP_CHOOSE_INDEP, 0, 0)], indep=True)
        assert_match(m, rid, 4)

    @pytest.mark.slow
    def test_failure_holes(self):
        """More shards than failure domains: indep emits NONE holes,
        firstn underfills — both must match the spec exactly."""
        m, root = builder.build_hierarchy(4, 2)
        ri = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
        assert_match(m, ri, 5)
        rf = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        assert_match(m, rf, 5)

    def test_weights_and_reweights(self):
        m, root = builder.build_flat(
            6, weights=[2 * WEIGHT_ONE, WEIGHT_ONE, WEIGHT_ONE, 0,
                        WEIGHT_ONE, WEIGHT_ONE // 2])
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        assert_match(m, rid, 3,
                     weights=[0x10000, 0x8000, 0x10000, 0x10000, 0, 0x4000])

    @pytest.mark.slow
    def test_out_of_range_device_rejected_both_paths(self):
        """A device id beyond the reweight vector is out (ref: mapper.c
        is_out item >= weight_max) — and BOTH compiled variants
        (skip_is_out True/False) must agree with the scalar spec, so a
        reweight flip cannot change placement of out-of-range ids
        (ADVICE r3 low #3)."""
        m, root = builder.build_flat(8)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        # 5-entry reweight vector: devices 5..7 are out-of-range
        full = [0x10000] * 5                    # skip_is_out compiles True
        assert_match(m, rid, 3, weights=full)
        mixed = [0x10000, 0x8000, 0x10000, 0x10000, 0x10000]  # general path
        assert_match(m, rid, 3, weights=mixed)

    def test_zero_weight_subtree(self):
        m, root = builder.build_hierarchy(
            4, 3, osd_weights=[0, 0, 0] + [WEIGHT_ONE] * 9)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        assert_match(m, rid, 3)

    @pytest.mark.slow
    def test_randomized_maps(self, rng):
        """Fuzz: random hierarchy shapes, algs, weights, rule kinds."""
        for trial in range(4):
            n_hosts = int(rng.integers(3, 9))
            per = int(rng.integers(1, 5))
            alg = [ALG_STRAW2, ALG_UNIFORM, ALG_LIST][trial % 3]
            weights = [int(w) for w in rng.integers(
                0, 4 * WEIGHT_ONE, size=n_hosts * per)]
            if alg == ALG_UNIFORM:
                weights = [WEIGHT_ONE] * (n_hosts * per)
            m, root = builder.build_hierarchy(n_hosts, per, alg=alg,
                                              osd_weights=weights)
            indep = bool(trial % 2)
            rid = builder.add_simple_rule(m, root, builder.TYPE_HOST,
                                          indep=indep)
            numrep = int(rng.integers(2, min(n_hosts, 6) + 1))
            xs = rng.integers(0, 2 ** 32, size=128, dtype=np.uint32)
            assert_match(m, rid, numrep, xs=xs)

    def test_device_weight_update_no_recompile(self):
        m, root = builder.build_flat(6)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        mapper = Mapper(m)
        xs = np.arange(64, dtype=np.uint32)
        a = np.asarray(mapper.map_pgs(rid, xs, 2))
        w = np.full(6, WEIGHT_ONE, dtype=np.int64)
        w[0] = 0
        mapper.set_device_weights(w)
        b = np.asarray(mapper.map_pgs(rid, xs, 2))
        assert not np.array_equal(a, b)
        assert 0 not in b

    def test_legacy_tunables_fall_back_to_scalar(self):
        """stable=0 / local-retries maps route through the scalar spec
        transparently (round 1 raised NotImplementedError)."""
        m, root = builder.build_flat(6, tunables=Tunables.legacy())
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        mapper = Mapper(m)
        assert mapper._scalar_reason
        xs = np.arange(64, dtype=np.uint32)
        got = np.asarray(mapper.map_pgs(rid, xs, 3))
        for i, x in enumerate(xs):
            ref = mapper_ref.do_rule(m, rid, int(x), 3)
            ref = ref + [ITEM_NONE] * (3 - len(ref))
            assert list(got[i]) == ref
        counts, bad = mapper.sweep(rid, 0, 64, 3)
        assert np.asarray(counts).sum() == (got != ITEM_NONE).sum()

    @pytest.mark.slow
    def test_straw_v1_matches_scalar(self):
        from ceph_tpu.crush.types import ALG_STRAW
        rng = np.random.default_rng(3)
        weights = [int(w) for w in rng.integers(
            1, 4 * WEIGHT_ONE, size=12)]
        m, root = builder.build_hierarchy(4, 3, alg=ALG_STRAW,
                                          osd_weights=weights)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        assert_match(m, rid, 3)

    @pytest.mark.slow
    def test_tree_matches_scalar(self):
        from ceph_tpu.crush.types import ALG_TREE
        rng = np.random.default_rng(4)
        weights = [int(w) for w in rng.integers(
            1, 4 * WEIGHT_ONE, size=10)]  # 5 hosts x 2: non-pow2 sizes
        m, root = builder.build_hierarchy(5, 2, alg=ALG_TREE,
                                          osd_weights=weights)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        assert_match(m, rid, 3)

    def test_straw_tree_distribution_weight_proportional(self):
        """Statistical: straw/tree selection tracks weights (the property
        the algorithms exist for), single-level argmax."""
        from ceph_tpu.crush.types import ALG_STRAW, ALG_TREE
        for alg in (ALG_STRAW, ALG_TREE):
            weights = [WEIGHT_ONE, 2 * WEIGHT_ONE, WEIGHT_ONE,
                       4 * WEIGHT_ONE]
            m, root = builder.build_flat(4, alg=alg, weights=weights)
            rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
            mapper = Mapper(m)
            xs = np.arange(8000, dtype=np.uint32)
            got = np.asarray(mapper.map_pgs(rid, xs, 1))[:, 0]
            counts = np.bincount(got, minlength=4).astype(float)
            frac = counts / counts.sum()
            want = np.asarray(weights, dtype=float)
            want /= want.sum()
            assert np.abs(frac - want).max() < 0.04, (alg, frac, want)


class TestChooseArgs:
    def _map_with_args(self, positions=1):
        from ceph_tpu.crush.types import ChooseArg
        m, root = builder.build_flat(6)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        ws = [[WEIGHT_ONE, WEIGHT_ONE, 3 * WEIGHT_ONE, WEIGHT_ONE,
               0, WEIGHT_ONE][:6] for _ in range(positions)]
        if positions > 1:
            ws[1] = [2 * WEIGHT_ONE] * 6
        m.choose_args[0] = {root: ChooseArg(weight_set=ws)}
        return m, rid, root

    def test_weight_set_changes_placement_and_matches_scalar(self):
        m, rid, root = self._map_with_args()
        xs = np.arange(256, dtype=np.uint32)
        plain = np.asarray(Mapper(m).map_pgs(rid, xs, 2))
        witharg = np.asarray(Mapper(m, choose_args=0).map_pgs(rid, xs, 2))
        assert not np.array_equal(plain, witharg)
        assert 4 not in witharg            # zero weight in the weight-set
        cargs = m.choose_args[0]
        for i, x in enumerate(xs):
            ref = mapper_ref.do_rule(m, rid, int(x), 2, choose_args=cargs)
            assert list(witharg[i]) == ref

    def test_multi_position_weight_set(self):
        m, rid, root = self._map_with_args(positions=2)
        xs = np.arange(128, dtype=np.uint32)
        got = np.asarray(Mapper(m, choose_args=0).map_pgs(rid, xs, 2))
        cargs = m.choose_args[0]
        for i, x in enumerate(xs):
            ref = mapper_ref.do_rule(m, rid, int(x), 2, choose_args=cargs)
            assert list(got[i]) == ref

    def test_ids_override_changes_hash(self):
        from ceph_tpu.crush.types import ChooseArg
        m, root = builder.build_flat(4)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        m.choose_args[0] = {root: ChooseArg(ids=[100, 101, 102, 103])}
        xs = np.arange(256, dtype=np.uint32)
        plain = np.asarray(Mapper(m).map_pgs(rid, xs, 1))
        withids = np.asarray(Mapper(m, choose_args=0).map_pgs(rid, xs, 1))
        assert not np.array_equal(plain, withids)
        cargs = m.choose_args[0]
        for i, x in enumerate(xs):
            ref = mapper_ref.do_rule(m, rid, int(x), 1, choose_args=cargs)
            assert list(withids[i]) == ref


class TestDerivedStateInvalidation:
    def test_straw_weight_adjust_recomputes(self):
        """Mutating a straw bucket's weight must recompute straws (ref:
        crush_bucket_adjust_item_weight recalculation)."""
        from ceph_tpu.crush.types import ALG_STRAW
        m, root = builder.build_flat(4, alg=ALG_STRAW)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        before = list(m.buckets[root].straws)
        builder.adjust_item_weight(m, 0, 8 * WEIGHT_ONE)
        after = list(m.buckets[root].straws)
        assert before != after
        assert_match(m, rid, 2)   # vectorized still matches the spec

    @pytest.mark.slow
    def test_tree_insert_adds_leaf(self):
        from ceph_tpu.crush.types import ALG_TREE
        m, root = builder.build_flat(4, alg=ALG_TREE)
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        m.max_devices = 5
        builder.insert_item(m, 4, WEIGHT_ONE, root)
        assert len(m.buckets[root].node_weights) >= 10
        mapper = Mapper(m)
        xs = np.arange(4096, dtype=np.uint32)
        got = np.asarray(mapper.map_pgs(rid, xs, 1))[:, 0]
        assert (got == 4).any()   # new item reachable
        assert_match(m, rid, 2)


class TestUniformFastPath:
    """The round-3 uniform-weight straw2 shortcut (argmax over raw
    hashes + ln-equality tie repair) must be bit-exact vs the scalar
    spec, including at engineered draw-tie collisions."""

    def test_ln_gap_info_invariants(self):
        from ceph_tpu.crush.ln_table import crush_ln, ln_gap_info
        G, zg = ln_gap_info()
        t = crush_ln(np.arange(0x10000, dtype=np.int64))
        d = np.diff(t)
        assert G == int(d[d > 0].min()) > 0
        assert np.array_equal(zg[:-1], d == 0)
        assert not zg[-1]
        # classes are adjacent pairs only
        runs = np.diff(np.where(d == 0)[0])
        assert not (runs == 1).any()

    def test_zg_tie_collision_matches_scalar(self):
        """x values engineered so two bucket items hash into one
        ln-equality pair with the LOWER value at an EARLIER index: a
        naive hash argmax would pick the wrong item; the scalar picks
        the first index of the draw-tie class."""
        m, root = builder.build_flat(8)           # uniform weights
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        mapper = Mapper(m)
        assert mapper._all_uniform
        xs = np.array([10232, 11311, 24792], dtype=np.uint32)
        got = np.asarray(mapper.map_pgs(rid, xs, 1))
        for i, x in enumerate(xs):
            ref = mapper_ref.do_rule(m, rid, int(x), 1)
            assert got[i, 0] == ref[0], (x, got[i, 0], ref)

    def test_uniform_flag_gating(self):
        from ceph_tpu.crush.ln_table import ln_gap_info
        G, _ = ln_gap_info()
        m, root = builder.build_flat(4)
        mapper = Mapper(m)
        assert mapper._all_uniform and mapper._skip_is_out
        # non-uniform weights -> general path
        m2, root2 = builder.build_flat(4)
        m2.buckets[root2].weights[0] = 3 * WEIGHT_ONE
        mp2 = Mapper(m2)
        assert not mp2._all_uniform
        # huge uniform weight above the ln-gap bound -> general path
        m3, root3 = builder.build_flat(4)
        for i in range(4):
            m3.buckets[root3].weights[i] = G + 1
        assert not Mapper(m3)._all_uniform
        # reweighted device -> is_out compiled back in
        w = np.full(4, WEIGHT_ONE, dtype=np.int64)
        w[1] = WEIGHT_ONE // 2
        mapper.set_device_weights(w)
        assert not mapper._skip_is_out

    def test_uniform_vs_scalar_randomized(self, rng):
        """Hierarchy of uniform-weight buckets: fast path everywhere,
        must match the scalar spec over a random x sample."""
        m, root = builder.build_hierarchy(8, 4, n_racks=2)
        rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
        mapper = Mapper(m)
        assert mapper._all_uniform
        xs = rng.integers(0, 1 << 30, 256).astype(np.uint32)
        got = np.asarray(mapper.map_pgs(rid, xs, 3))
        for i, x in enumerate(xs):
            ref = mapper_ref.do_rule(m, rid, int(x), 3)
            ref = ref + [ITEM_NONE] * (3 - len(ref))
            assert list(got[i]) == ref, (x,)


# ---------------------------------------------------------------------------
# The width of a block: chosen from the lanes left to map (block_width)
# ---------------------------------------------------------------------------

_CAP = 1 << 9            # the small explicit cap
_LOW_FLOOR = 1 << 6      # a floor under it, so 64..512 are all in play
_WRAP = (1 << 32) - 300  # a start whose range crosses 2^32
_SIZES = {"1": lambda cap: 1, "255": lambda cap: 255,
          "256": lambda cap: 256, "257": lambda cap: 257,
          "cap": lambda cap: cap, "cap+1": lambda cap: cap + 1,
          "2cap+3": lambda cap: 2 * cap + 3}
# mode -> (map, Mapper's block, the floor the case runs under)
_MODES = {
    # cap under the floor: an explicit block below the floor wins
    "explicit": (lambda: builder.build_hierarchy(6, 4), _CAP, None),
    # cap above the floor: every width between the two is a program
    "lowfloor": (lambda: builder.build_hierarchy(6, 4), _CAP, _LOW_FLOOR),
    # the block Mapper sizes by itself (a wide bucket keeps it small
    # enough for the CPU: 2^15 lanes)
    "auto": (lambda: builder.build_flat(100), None, None),
}
_width_cases: dict = {}


def _set_floor(monkeypatch, floor):
    from ceph_tpu.crush import mapper as mapper_mod
    if floor is not None:
        monkeypatch.setattr(mapper_mod, "MIN_BLOCK_WIDTH", floor)
    return mapper_mod.MIN_BLOCK_WIDTH


def _width_case(mode, indep, start):
    """(mapper, rule, numrep, cap, every mapping of the longest range
    from ``start``), built once a (mode, rule kind, start): the cases of
    one share its compiled programs. The mappings are ``map_pgs``'s,
    themselves held to ``mapper_ref.do_rule`` on a sample."""
    key = (mode, indep, start)
    if key not in _width_cases:
        build, block, _floor = _MODES[mode]
        m, root = build()
        leaf = builder.TYPE_OSD if mode == "auto" else builder.TYPE_HOST
        rid = builder.add_simple_rule(m, root, leaf, indep=indep)
        numrep = 4 if indep else 3
        mapper = Mapper(m, block=block)
        cap = mapper.effective_block(rid, numrep)
        assert cap == mapper.block      # the XLA path: no wider cap
        xs = ((start + np.arange(2 * cap + 3, dtype=np.uint64))
              % (1 << 32)).astype(np.uint32)
        ref = np.asarray(mapper.map_pgs(rid, xs, numrep))
        sample = sorted({0, 1, 299, 300, 301, cap - 1, cap, 2 * cap,
                         2 * cap + 2})
        for i in sample:
            want = mapper_ref.do_rule(m, rid, int(xs[i]), numrep)
            want = want + [ITEM_NONE] * (numrep - len(want))
            assert list(ref[i]) == want, (int(xs[i]), list(ref[i]), want)
        _width_cases[key] = (mapper, rid, numrep, cap, ref)
    return _width_cases[key]


def _sweep_lanes_want(n, cap, floor):
    from ceph_tpu.crush.mapper import block_width
    k, r = divmod(n, cap)
    return k * cap + (block_width(r, cap, floor) if r else 0), \
        k + (1 if r else 0)


@pytest.mark.parametrize("size", list(_SIZES))
@pytest.mark.parametrize("indep", [False, True], ids=["firstn", "indep"])
@pytest.mark.parametrize("mode,start", [
    ("explicit", 12345), ("explicit", _WRAP),
    ("lowfloor", 12345), ("lowfloor", _WRAP), ("auto", _WRAP)])
def test_sweep_blocks_fit_the_lanes(monkeypatch, mode, start, indep, size):
    """A sweep's (counts, bad) are the bincount of ``map_pgs`` over the
    same range whatever widths its blocks took, and the lanes it
    dispatched are the width rule's: under the cap one block of the
    next power of two (at least the floor), above it whole blocks and a
    tail block as wide as the remainder needs."""
    from ceph_tpu.crush.mapper import PERF
    floor = _set_floor(monkeypatch, _MODES[mode][2])
    mapper, rid, numrep, cap, ref = _width_case(mode, indep, start)
    n = _SIZES[size](cap)
    before = PERF.dump()
    counts, bad = mapper.sweep(rid, start, n, numrep)
    counts = np.asarray(counts)
    after = PERF.dump()
    live = ref[:n] != ITEM_NONE
    want = np.bincount(ref[:n][live], minlength=mapper.packed.max_devices)
    assert np.array_equal(counts, want)
    assert int(counts.sum()) == int(live.sum())
    want_bad = 0 if indep else int((live.sum(axis=1) < numrep).sum())
    assert int(bad) == want_bad
    lanes = after["sweep_lanes"] - before["sweep_lanes"]
    want_lanes, want_blocks = _sweep_lanes_want(n, cap, floor)
    assert lanes == want_lanes
    assert after["sweep_blocks"] - before["sweep_blocks"] == want_blocks
    assert after["pgs_mapped"] - before["pgs_mapped"] == n
    if n < cap:
        assert n <= lanes < 2 * max(n, floor)


def test_sweep_compiles_one_program_a_width(monkeypatch):
    """Sweeping every n of 1..cap compiles no more programs than the
    rule has widths between the floor and the cap, and dispatches the
    width rule's lanes for each."""
    from ceph_tpu.crush.mapper import PERF, block_width
    cap, floor = 1 << 7, _set_floor(monkeypatch, 1 << 4)
    m, root = builder.build_hierarchy(5, 3)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
    mapper = Mapper(m, block=cap)
    widths = [block_width(n, cap, floor) for n in range(1, cap + 1)]
    assert set(widths) == {16, 32, 64, 128}
    before = PERF.dump()
    for n in range(1, cap + 1):
        counts, bad = mapper.sweep(rid, 7, n, 2)
    assert int(np.asarray(counts).sum()) == 2 * cap and int(bad) == 0
    after = PERF.dump()
    assert after["sweep_compiles"] - before["sweep_compiles"] \
        <= len(set(widths))
    assert after["sweep_lanes"] - before["sweep_lanes"] == sum(widths)
    assert after["pgs_mapped"] - before["pgs_mapped"] \
        == cap * (cap + 1) // 2


@pytest.mark.parametrize("lanes,cap,floor,want", [
    (1, 1 << 21, 1 << 16, 1 << 16),
    ((1 << 16) + 1, 1 << 21, 1 << 16, 1 << 17),
    (1 << 20, 1 << 21, 1 << 16, 1 << 20),          # crushtool-10k-1m
    ((1 << 20) + 1, 1 << 21, 1 << 16, 1 << 21),
    (1 << 21, 1 << 21, 1 << 16, 1 << 21),          # a pod device's share
    (100_000_000, 1 << 21, 1 << 16, 1 << 21),
    (300, 1 << 9, 1 << 16, 1 << 9),                # explicit block wins
    (600, 1000, 1, 1000),                          # never above the cap
    (np.int64(300), 1 << 14, 1, 1 << 9),
    (0, 1 << 14, 1, 1), (1, 1 << 14, 1, 1), (2, 1 << 14, 1, 2),
    (3, 1 << 14, 1, 4)])
def test_block_width(lanes, cap, floor, want):
    from ceph_tpu.crush.mapper import block_width
    assert block_width(lanes, cap, floor) == want


def test_kernel_path_has_six_widths():
    """The floor and the kernel path's cap bound what one Mapper can
    compile for sweeps: one program a power of two from 2^16 to 2^21."""
    from ceph_tpu.crush.mapper import MIN_BLOCK_WIDTH, block_width
    widths = {block_width(n, 1 << 21, MIN_BLOCK_WIDTH)
              for e in range(23) for n in ((1 << e) - 1, 1 << e,
                                           (1 << e) + 1)}
    assert sorted(widths) == [1 << e for e in range(16, 22)]


def test_sweep_kernel_path_narrow_block(monkeypatch):
    """The fused kernel (interpret mode) under the same rule: a sweep
    of 257 lanes runs one 512-lane block, not the kernel path's 2^21,
    and counts what the XLA path counts."""
    from ceph_tpu.crush.mapper import PERF
    _set_floor(monkeypatch, _LOW_FLOOR)
    m, root = builder.build_hierarchy(6, 4)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
    monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "interpret")
    mk = Mapper(m, block=_CAP)
    assert mk._kernel_body(rid, 3) is not None
    assert mk.effective_block(rid, 3) == 1 << 21
    mx, _rid, _numrep, _cap, ref = _width_case("lowfloor", False, 12345)
    before = PERF.dump()
    counts, bad, path = mk.sweep_path(rid, 12345, 257, 3)
    after = PERF.dump()
    assert path == "pallas-interpret"
    assert after["sweep_lanes"] - before["sweep_lanes"] == 512
    assert after["sweep_blocks"] - before["sweep_blocks"] == 1
    assert np.array_equal(
        np.asarray(counts),
        np.bincount(ref[:257].reshape(-1), minlength=m.max_devices))
    assert int(bad) == 0


def test_map_pgs_tail_block_is_as_wide_as_its_lanes(monkeypatch):
    """``map_pgs`` past the cap pads its tail block to the width the
    remainder needs and returns exactly the rows asked for."""
    _set_floor(monkeypatch, _LOW_FLOOR)
    mapper, rid, numrep, cap, ref = _width_case("lowfloor", False, 12345)
    from ceph_tpu.utils.devmon import devmon
    xs = ((12345 + np.arange(2 * cap + 3, dtype=np.uint64))
          % (1 << 32)).astype(np.uint32)
    seen = []
    real = devmon().jit_call

    def spy(name, key, fn, *args):
        seen.append((name, key[-1], args[-1].shape[0]))
        return real(name, key, fn, *args)

    monkeypatch.setattr(devmon(), "jit_call", spy)
    for n, widths in ((cap + 1, [cap, 64]), (cap + 65, [cap, 128]),
                      (2 * cap, [cap, cap]), (2 * cap + 3, [cap, cap, 64])):
        seen.clear()
        got = np.asarray(mapper.map_pgs(rid, xs[:n], numrep))
        assert got.shape == (n, numrep) and np.array_equal(got, ref[:n])
        assert [s[2] for s in seen] == widths
        assert [s[1] for s in seen] == widths     # the jit key's width

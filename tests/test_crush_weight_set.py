"""A map that carries a weight-set (``choose_args``): ``crushtool
--test`` honours it as upstream's tool does, the builder installs one
as ``CrushWrapper`` does, and the fused kernel's sweep counts what its
flagged-lane fallback did.

The tester's maps run the XLA path (the CPU has no kernel); the three
counter tests run the kernel through the Pallas interpreter on a
one-level map, 2 s a program.
"""

import json

import numpy as np
import pytest

from ceph_tpu.bench import crushtool
from ceph_tpu.crush import builder, mapper as mapper_mod, mapper_ref
from ceph_tpu.crush import hash as crush_hash
from ceph_tpu.crush.ln_table import crush_ln
from ceph_tpu.crush.mapper import KERNEL_TALLY, PERF, Mapper
from ceph_tpu.crush.tester import CrushTester
from ceph_tpu.crush.types import ITEM_NONE, WEIGHT_ONE, ChooseArg
from ceph_tpu.encoding import decode_crush_map, encode_crush_map

XS = 512


def _map():
    m, root = builder.build_hierarchy(8, 4, n_racks=4)
    rid = builder.add_simple_rule(m, root, builder.TYPE_RACK)
    return m, rid


def _install(m, key, seed):
    """A one-position weight-set under ``key``, every OSD's weight its
    own, through the builder's helpers."""
    builder.create_choose_args(m, key, 1)
    rng = np.random.default_rng(seed)
    builder.choose_args_set_item_weights(
        m, key, {osd: [int(WEIGHT_ONE * rng.uniform(0.5, 1.5))]
                 for osd in range(m.max_devices)})


def _ref_rows(m, rid, key, xs, num_rep=3):
    args = m.choose_args.get(key) if key is not None else None
    rows = []
    for x in xs:
        got = mapper_ref.do_rule(m, rid, int(x), num_rep, choose_args=args)
        rows.append(got + [ITEM_NONE] * (num_rep - len(got)))
    return np.array(rows)


KEYS = [((0,), 0), ((-1,), -1), ((0, -1), 0), ((), None), ((7,), None),
        ((7, -1), -1)]


@pytest.mark.parametrize("keys,served", KEYS, ids=[str(k) for k, _ in KEYS])
def test_the_tester_maps_with_the_weight_set_upstream_would_pick(keys,
                                                                 served):
    """CrushTester::test calls do_rule(..., 0): the set of id 0, else
    the compat set, else none; a pool's set of another id serves no
    ``--test``."""
    m, rid = _map()
    for key in keys:
        _install(m, key, seed=100 + key)
    tester = CrushTester(m, batch=XS)
    assert tester.choose_args_key == served
    assert tester.mapper.choose_args_key == served
    res = tester.test(rid, 3, 0, XS - 1, keep_mappings=True)
    assert res.choose_args == served
    want = _ref_rows(m, rid, served, range(XS))
    assert np.array_equal(res.mappings, want)
    # the aggregated sweep is the same placement, counted
    swept = tester.test(rid, 3, 0, XS - 1)
    assert np.array_equal(swept.device_counts,
                          np.bincount(want.ravel(), minlength=32))
    if served is not None:
        # and not the unbalanced tree's: the weight-set moved mappings
        assert (want != _ref_rows(m, rid, None, range(XS))).any()


@pytest.mark.parametrize("key", [-1, None], ids=["compat", "none"])
def test_crushtool_test_honours_it_with_no_flag_and_says_which(key, tmp_path,
                                                               capsys):
    m, rid = _map()
    if key is not None:
        _install(m, key, seed=5)
    path = tmp_path / "crush.map"
    path.write_bytes(encode_crush_map(m))
    out = crushtool.main(["-i", str(path), "--test", "--rule", str(rid),
                          "--num-rep", "3", "--min-x", "0", "--max-x",
                          str(XS - 1), "--batch", str(XS),
                          "--show-statistics", "--json"])
    said = "none" if key is None else key
    assert out["choose_args"] == said
    printed = capsys.readouterr().out
    assert f"choose_args {said}" in printed.splitlines()[0]
    assert json.loads(printed.splitlines()[-1])["choose_args"] == said
    want = _ref_rows(m, rid, key, range(XS))
    assert out["utilization"]["placements"] == 3 * XS
    assert out["utilization"]["max"] == np.bincount(want.ravel()).max()


def test_the_osdmap_and_the_tester_share_one_rule():
    m, _rid = _map()
    assert m.choose_args_with_fallback(0) is None
    m.choose_args[-1] = {}
    assert m.choose_args_with_fallback(0) == -1
    assert m.choose_args_with_fallback(3) == -1
    m.choose_args[3] = {}
    assert m.choose_args_with_fallback(3) == 3
    assert m.choose_args_with_fallback(0) == -1


# -- installing a weight-set -------------------------------------------------

def _sums_hold(m, key):
    args = m.choose_args[key]
    for bid, b in m.buckets.items():
        for pos, ws in enumerate(args[bid].weight_set):
            assert len(ws) == b.size
            for i, item in enumerate(b.items):
                if item < 0:
                    assert ws[i] == sum(args[item].weight_set[pos]), \
                        (bid, item)


@pytest.mark.parametrize("positions", [1, 3])
def test_a_new_weight_set_is_the_crush_weights(positions):
    m, _rid = _map()
    builder.create_choose_args(m, -1, positions)
    assert set(m.choose_args[-1]) == set(m.buckets)
    for bid, b in m.buckets.items():
        assert m.choose_args[-1][bid].weight_set == [b.weights] * positions
        assert m.choose_args[-1][bid].weight_set[0] is not b.weights
        assert m.choose_args[-1][bid].ids is None
    _sums_hold(m, -1)
    with pytest.raises(ValueError):
        builder.create_choose_args(m, -1, positions)


def test_adjusting_an_item_carries_its_buckets_sum_to_every_ancestor():
    m, _rid = _map()
    builder.create_choose_args(m, -1, 1)
    host, rack, root = -1, -9, -13
    assert m.buckets[host].items[2] == 2 and host in m.buckets[rack].items
    changed = builder.choose_args_adjust_item_weight(m, -1, 2, [0x8000])
    assert changed == 3                  # the OSD, its host, its rack
    args = m.choose_args[-1]
    assert args[host].weight_set == [[0x10000, 0x10000, 0x8000, 0x10000]]
    assert args[rack].weight_set[0][m.buckets[rack].items.index(host)] \
        == 0x38000
    assert args[root].weight_set[0] == [0x78000, 0x80000, 0x80000, 0x80000]
    # a bucket's own entry can be set too; the CRUSH weights never move
    builder.choose_args_adjust_item_weight(m, -1, host, [0x30000])
    assert args[root].weight_set[0][0] == 0x70000
    assert m.buckets[root].weights == [0x80000] * 4
    assert m.buckets[host].weights == [0x10000] * 4
    with pytest.raises(ValueError):
        builder.choose_args_adjust_item_weight(m, -1, 99, [1])
    with pytest.raises(ValueError):      # one weight a position
        builder.choose_args_adjust_item_weight(m, -1, 2, [1, 2])


@pytest.mark.parametrize("bulk", [True, False], ids=["bulk", "one_by_one"])
@pytest.mark.parametrize("positions", [1, 2])
def test_an_installed_weight_set_survives_the_binary_map_bit_for_bit(
        positions, bulk):
    m, _rid = _map()
    builder.create_choose_args(m, -1, positions)
    rng = np.random.default_rng(9)
    weights = {osd: [int(v) for v in rng.integers(1, 1 << 20, positions)]
               for osd in range(m.max_devices)}
    if bulk:
        changed = builder.choose_args_set_item_weights(m, -1, weights)
    else:
        changed = sum(builder.choose_args_adjust_item_weight(m, -1, osd, ws)
                      for osd, ws in weights.items())
    assert changed == 3 * m.max_devices
    _sums_hold(m, -1)
    back = decode_crush_map(encode_crush_map(m))
    assert set(back.choose_args) == {-1}
    for bid in m.buckets:
        assert back.choose_args[-1][bid].weight_set == \
            m.choose_args[-1][bid].weight_set
    _sums_hold(back, -1)
    assert encode_crush_map(back) == encode_crush_map(m)


def test_a_bucket_the_set_lacks_starts_from_its_crush_weights():
    """The mgr writes vectors for device-holding buckets only: adjusting
    an OSD there gives every ancestor a vector on first touch."""
    m, _rid = _map()
    m.choose_args[-1] = {-1: ChooseArg(weight_set=[[1, 2, 3, 4]])}
    builder.choose_args_adjust_item_weight(m, -1, 0, [5])
    args = m.choose_args[-1]
    assert args[-1].weight_set == [[5, 2, 3, 4]]
    assert args[-9].weight_set[0][m.buckets[-9].items.index(-1)] == 14
    assert args[-13].weight_set[0][0] == 14 + 0x40000


# -- what the kernel's fallback did -------------------------------------------

HEAVY = [(1 << 29) + 7919 * i for i in range(8)]
LANES = 1 << 16                          # one sweep block, the floor


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "interpret")


def _flat(weights):
    m, root = builder.build_flat(8, weights=weights)
    return m, builder.add_simple_rule(m, root, builder.TYPE_OSD)


def _swept(mp, rid, n=LANES):
    before = PERF.dump()
    counts, _bad, path = mp.sweep_path(rid, 0, n, 1)
    after = PERF.dump()
    assert path == "pallas-interpret"
    tally = {k: after[k] - before[k]
             for k in KERNEL_TALLY + ("sweep_lanes",)}
    return np.asarray(counts), tally


def _ref_counts(m, rid, n):
    counts = np.zeros(m.max_devices, dtype=np.int64)
    for x in range(n):
        counts[mapper_ref.do_rule(m, rid, x, 1)[0]] += 1
    return counts


def _tied_lanes(weights, n):
    """Lanes whose two best straw2 draws at r = 0 are the same integer:
    inside the kernel's margin whatever its width."""
    x = np.arange(n, dtype=np.uint32)[:, None]
    items = np.arange(8, dtype=np.uint32)[None, :]
    u = crush_hash.hash32_3(x, items, np.uint32(0)) & np.uint32(0xFFFF)
    neg = (1 << 48) - crush_ln(u).astype(np.int64)
    draws = np.sort(neg // np.array(weights, dtype=np.int64), axis=1)
    return np.nonzero(draws[:, 0] == draws[:, 1])[0]


def test_a_tie_inside_the_margin_flags_its_lane(interpret):
    """Weights near 2^29 draw quotients of a few thousand, so two slots
    land on one integer every few thousand lanes: each such lane is
    flagged, recomputed, and counted."""
    m, rid = _flat(HEAVY)
    mp = Mapper(m)
    assert mp._kernel_plan(rid).kmax == (0,)        # the continuous draw
    tied = _tied_lanes(HEAVY, LANES)
    assert len(tied) >= 3
    counts, tally = _swept(mp, rid)
    # what the kernel itself flags, lane by lane
    import jax
    import jax.numpy as jnp
    from ceph_tpu.crush import pallas_mapper as pm
    with jax.enable_x64(True):
        _, bad = pm._run_kernel(mp._kernel_plan(rid),
                                jnp.arange(LANES, dtype=jnp.int32), 1,
                                interpret=True)
    bad = np.asarray(bad).astype(bool)
    assert bad[tied].all()
    assert tally == {"kernel_flagged_lanes": int(bad.sum()),
                     "kernel_fallback_blocks": 1,
                     "kernel_fallback_overflows": 0, "sweep_lanes": LANES}
    assert len(tied) <= tally["kernel_flagged_lanes"] < 256
    n = 4096
    counts, _ = _swept(mp, rid, n)
    assert np.array_equal(counts, _ref_counts(m, rid, n))


def test_a_uniform_map_flags_none(interpret):
    """An all-uniform plan draws with no margin: its sweep program
    carries no tally and no counter moves."""
    m, rid = _flat([WEIGHT_ONE] * 8)
    mp = Mapper(m)
    assert mp._kernel_plan(rid).kmax == (1,)
    assert mp._kernel_plan(rid).rhlh is None
    counts, tally = _swept(mp, rid, 4096)
    assert tally == {"kernel_flagged_lanes": 0, "kernel_fallback_blocks": 0,
                     "kernel_fallback_overflows": 0, "sweep_lanes": LANES}
    assert np.array_equal(counts, _ref_counts(m, rid, 4096))


def test_flags_beyond_the_buffer_count_an_overflow_and_map_the_same(
        interpret, monkeypatch):
    """The buffer forced down to 4 lanes: the block's flags overflow it,
    the buffer takes as many passes as the flags need, and the mappings
    are still the scalar spec's."""
    monkeypatch.setattr(mapper_mod, "fallback_lanes", lambda n: 4)
    m, rid = _flat(HEAVY)
    n = 4096
    counts, tally = _swept(Mapper(m), rid, n)
    assert tally["kernel_flagged_lanes"] > 4
    assert tally["kernel_fallback_blocks"] == 1
    assert tally["kernel_fallback_overflows"] == 1
    assert np.array_equal(counts, _ref_counts(m, rid, n))


NARROW = [(), (64, 8), (128,), (8,)]


@pytest.mark.parametrize("narrow", NARROW, ids=[str(n) for n in NARROW])
def test_a_replica_slot_finished_in_narrower_blocks_maps_the_same(narrow):
    """The recompute's loop goes on in narrower blocks once few lanes
    have a try left (``_choose_one_firstn``'s ``narrow``): 3 replicas
    over 4 racks collide on every other lane, so the slots' later
    rounds run gathered, and every lane is still the scalar spec's."""
    import jax
    import jax.numpy as jnp
    m, rid = _map()
    _install(m, -1, 5)
    mp = Mapper(m, choose_args=-1)
    root = m.rules[rid].steps[0].arg1
    cfg = dict(mp.cfg, levels_main=1, levels_leaf=2)
    n = 512

    def three(arrs, xs):
        rows = jnp.full(n, -1 - root, dtype=jnp.int32)
        out = jnp.full((n, 3), ITEM_NONE, dtype=jnp.int32)
        leaves = out
        for rep in range(3):
            item, leaf, ok = mapper_mod._choose_one_firstn(
                arrs, cfg, rows, jnp.ones(n, dtype=bool), xs, rep,
                out[:, :rep], leaves[:, :rep], builder.TYPE_RACK, True,
                m.tunables.choose_total_tries, 1,
                m.tunables.chooseleaf_vary_r, narrow=narrow)
            out = out.at[:, rep].set(jnp.where(ok, item, ITEM_NONE))
            leaves = leaves.at[:, rep].set(jnp.where(ok, leaf, ITEM_NONE))
        return leaves

    with jax.enable_x64(True):
        xs = jnp.arange(n, dtype=jnp.uint32)
        text = jax.jit(three).lower(mp.arrays, xs).as_text()
        got = np.asarray(jax.jit(three)(mp.arrays, xs))
    # one top_k a slot and width
    assert text.count("chlo.top_k") == 3 * len(narrow)
    assert np.array_equal(got, _ref_rows(m, rid, -1, range(n)))


DEAD = [
    ("base", [WEIGHT_ONE, 0], None),
    ("base", [WEIGHT_ONE, 0, WEIGHT_ONE, WEIGHT_ONE], None),
    ("weight_set", [WEIGHT_ONE] * 2, [WEIGHT_ONE, 0]),
    ("weight_set", [WEIGHT_ONE] * 4, [0, 0x18000, 0x18000, 0x18000]),
]


@pytest.mark.parametrize("where,weights,vector", DEAD,
                         ids=[f"{w}-{len(ws)}" for w, ws, _ in DEAD])
def test_a_zero_weight_slot_beside_one_weight_class_never_wins(
        interpret, where, weights, vector):
    """One weight class and a dead slot (an OSD at CRUSH weight 0, a
    drained entry of a weight-set): the kernel's uniform layout cannot
    say a slot is dead, so the level takes the per-slot layout. Found
    by the benchmark's two-OSD probe: the uniform layout placed half
    the inputs on the dead OSD."""
    m, root = builder.build_flat(len(weights), weights=weights)
    rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
    if vector is not None:
        m.choose_args[-1] = {root: ChooseArg(weight_set=[vector])}
    tester = CrushTester(m, batch=256)
    assert tester.mapper._kernel_plan(rid).kmax == (0,)
    res = tester.test(rid, 1, 0, 255, keep_mappings=True)
    assert res.path == "pallas-interpret"
    want = _ref_rows(m, rid, tester.choose_args_key, range(256), 1)
    assert np.array_equal(res.mappings, want)
    dead = (vector or weights).index(0)
    assert res.device_counts[dead] == 0


# -- the general path's draw: crush_ln by the kernel's ladder -----------------

LN_PLANES = [((2048, 32), None), ((16384, 4), None), ((2048, 32), 5000)]


@pytest.mark.parametrize("shape,row", LN_PLANES,
                         ids=["32-slot", "4-slot", "in-rows"])
def test_the_general_draw_ln_is_crush_ln_over_its_whole_domain(
        monkeypatch, shape, row):
    """Every 16-bit hash, laid out as the (lanes, slots) plane
    ``_straw2_choose`` hands the ladder, is 2^48 - crush_ln(u) bit for
    bit; also where a plane wider than ``_LN_ROW`` goes through in
    rows (the last one padded)."""
    import jax
    import jax.numpy as jnp
    if row is not None:
        monkeypatch.setattr(mapper_mod, "_LN_ROW", row)
    rhlh, ll, _zg = mapper_mod._staged_const_tables()
    arrs = {"ln_rhlh": rhlh, "ln_ll": ll}
    u = np.arange(0x10000, dtype=np.int64)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(
            lambda v: mapper_mod._straw2_neg(arrs, v))(
                jnp.asarray(u.reshape(shape), dtype=jnp.int32)))
    assert got.shape == shape and got.dtype == np.uint64
    want = (1 << 48) - crush_ln(u)
    assert np.array_equal(got.reshape(-1).astype(np.int64), want)


def test_the_general_draw_on_a_weight_set_map_is_the_scalar_specs():
    """The rule VM draws by ``_straw2_choose`` at all three levels (a
    rack of four, a host of two, an OSD of four) on a map whose compat
    weight-set weighs host0's OSDs and osd.17 0: a zero-weight slot at
    the rack and at the host level. Every position of 2,048 inputs
    spread over the id space is ``mapper_ref``'s, and none lands on a
    zero-weight OSD."""
    m, rid = _map()
    _install(m, -1, 11)
    dead = [0, 1, 2, 3, 17]
    builder.choose_args_set_item_weights(m, -1, {o: [0] for o in dead})
    mp = Mapper(m, choose_args=-1, block=4096)
    assert mp.mapping_path(rid, 3) == "xla"
    xs = ((np.arange(2048, dtype=np.uint64) * 2654435761) % (1 << 32)
          ).astype(np.uint32)
    got = np.asarray(mp.map_pgs(rid, xs, 3))
    assert np.array_equal(got, _ref_rows(m, rid, -1, xs))
    assert not np.isin(got, dead).any()

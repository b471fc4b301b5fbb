"""Mesh-sharded CRUSH sweep (crush/sharded_sweep.py): bit-exactness
vs the single-device engine on the 8-device virtual CPU mesh.

The pod-scale claim rests on the sharded sweep being the SAME
computation as the single-chip path, only split over the mesh axis —
every test here pins lane-for-lane equality against ``Mapper.map_pgs``
/ ``Mapper.sweep`` (and through them ``mapper_ref``), across shard
boundaries, non-divisible batch padding, zero-weight slots,
choose_args weight-sets, and the kernel's ambiguity-flagged fallback
lanes. Multichip behavior is guarded by n_devices detection: CI runs
XLA's 8-virtual-device CPU mesh (conftest forces it), the same
shardings the driver's dryrun and the TPU bench use.

Budget note: the per-test cost here is XLA CPU compiles of 8-shard
programs, so tests share one module-scope map/mapper and matched
(block, local_n) shapes wherever exactness allows — the shard_map
executables then reuse across tests instead of recompiling.
"""

import numpy as np
import pytest

from ceph_tpu.crush import builder, mapper_ref
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.sharded_sweep import sharded_map_pgs, sharded_sweep
from ceph_tpu.crush.types import ITEM_NONE, WEIGHT_ONE
from ceph_tpu.parallel import local_mesh

N = 8 * 97          # shard-boundary-rich, non-divisible by block


@pytest.fixture(scope="module")
def mesh():
    m = local_mesh()
    # the tier-1 fallback contract: XLA_FLAGS virtualizes 8 CPU
    # devices (conftest); real multichip runs detect their own count
    assert m.devices.size == 8
    return m


def _hier(n_hosts, per_host, weights=None):
    m, root = builder.build_hierarchy(
        n_hosts, per_host, n_racks=max(1, n_hosts // 4),
        osd_weights=weights)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
    return m, rid


@pytest.fixture(scope="module")
def hier(mesh):
    """One shared (map, rule, mapper, reference table) for every test
    that doesn't need special weights — the compiled shard programs
    and the single-device reference amortize across the module."""
    m, rid = _hier(8, 4)
    mp = Mapper(m, block=1 << 10)
    xs = np.arange(N, dtype=np.uint32)
    want = np.asarray(mp.map_pgs(rid, xs, 3))
    return m, rid, mp, want


def _assert_rows_match_ref(m, rid, got, xs, numrep, weights=None,
                           choose_args=None):
    wl = list(weights) if weights is not None else None
    for i, x in enumerate(xs):
        ref = mapper_ref.do_rule(m, rid, int(x), numrep, weight=wl,
                                 choose_args=choose_args)
        ref = ref + [ITEM_NONE] * (numrep - len(ref))
        assert list(got[i]) == ref, (int(x), list(got[i]), ref)


class TestBitExact:
    def test_map_pgs_matches_single_device_and_ref_at_boundaries(
            self, mesh, hier):
        """Shard-boundary PG ids must not smear: the lanes at every
        shard edge are checked against the scalar spec directly, and
        the whole table against the single-device engine."""
        m, rid, mp, want = hier
        xs = np.arange(N, dtype=np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        assert (got == want).all()
        local_n = N // 8
        edges = sorted({0, N - 1} | {
            b for s in range(1, 8) for b in
            (s * local_n - 1, s * local_n)})
        _assert_rows_match_ref(m, rid, got[edges], xs[edges], 3)

    def test_non_divisible_batch_padding(self, mesh, hier):
        """n % n_devices != 0 pads (map) / tail-masks (sweep) — both
        entry points stay exact at an awkward size."""
        m, rid, mp, want = hier
        n = 757                           # prime: 757 % 8 == 5
        xs = np.arange(n, dtype=np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        assert (got == want[:n]).all()
        c, b = sharded_sweep(mesh, mp, rid, 0, n, 3)
        c1, b1 = mp.sweep(rid, 0, n, 3)
        assert (np.asarray(c) == np.asarray(c1)).all()
        assert int(b) == int(b1)

    @pytest.mark.parametrize("sharded", [False, True])
    def test_sweep_range_wraps_at_2_pow_32(self, mesh, hier, sharded):
        """x is crush_do_rule's 32-bit input: a sweep whose start has
        run past 2^32 (a benchmark window stepping its start by 2^23 a
        sweep gets there in 40 s once a sweep takes 85 ms) maps the low
        word, and one that crosses 2^32 goes on from 0, as the
        benchmark's reference counts them."""
        m, rid, mp, want = hier

        def sweep(start, n):
            c, b = (sharded_sweep(mesh, mp, rid, start, n, 3) if sharded
                    else mp.sweep(rid, start, n, 3))
            return np.asarray(c), int(b)

        want_c = np.bincount(want[:757].reshape(-1),
                             minlength=m.max_devices)
        c, b = sweep((1 << 32) + (1 << 33), 757)
        assert (c == want_c).all() and b == 0
        head, _ = sweep((1 << 32) - 300, 300)
        c, b = sweep((1 << 32) - 300, 300 + 757)
        assert (c == head + want_c).all() and b == 0

    def test_randomized_sweep(self, mesh, hier, rng):
        """Randomized PG ids (not a contiguous range) through the
        sharded full-mapping path vs the single-device engine."""
        m, rid, mp, _ = hier
        xs = rng.integers(0, 1 << 31, size=N).astype(np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        want = np.asarray(mp.map_pgs(rid, xs, 3))
        assert (got == want).all()

    def test_zero_weight_slots(self, mesh):
        """Zero-weight OSDs (dead slots in their host buckets) must
        never be chosen, sharded or not."""
        weights = [0 if i % 5 == 0 else WEIGHT_ONE for i in range(16)]
        m, rid = _hier(4, 4, weights=weights)
        mp = Mapper(m, block=1 << 10)
        xs = np.arange(203, dtype=np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        want = np.asarray(mp.map_pgs(rid, xs, 3))
        assert (got == want).all()
        dead = [i for i in range(16) if weights[i] == 0]
        assert not (np.isin(got, dead)).any()
        _assert_rows_match_ref(m, rid, got[:16], xs[:16], 3)

    def test_choose_args_weight_sets(self, mesh):
        """A balancer-style single-position choose_args weight-set
        rides the sharded path bit-exactly (the XLA engine here; the
        kernel variant is TestKernelPath)."""
        from ceph_tpu.crush.types import ChooseArg
        m, rid = _hier(4, 5)
        rng = np.random.default_rng(7)
        args = {}
        for bid, b in m.buckets.items():
            scale = rng.uniform(0.9, 1.1, size=b.size)
            args[bid] = ChooseArg(weight_set=[[
                max(1, int(w * s))
                for w, s in zip(b.weights, scale)]])
        m.choose_args[0] = args
        mp = Mapper(m, block=1 << 10, choose_args=0)
        xs = np.arange(203, dtype=np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        want = np.asarray(mp.map_pgs(rid, xs, 3))
        assert (got == want).all()
        _assert_rows_match_ref(m, rid, got[:16], xs[:16], 3,
                               choose_args=args)

    def test_legacy_tunables_rejected(self, mesh):
        from ceph_tpu.crush.types import Tunables
        m, rid = _hier(4, 2)
        m.tunables = Tunables(chooseleaf_stable=0)
        mp = Mapper(m)
        with pytest.raises(ValueError):
            sharded_map_pgs(mesh, mp, rid,
                            np.arange(64, dtype=np.uint32), 3)
        with pytest.raises(ValueError):
            sharded_sweep(mesh, mp, rid, 0, 64, 3)


class TestShardWidths:
    """The per-shard (local_n, block) are ``mapper.block_width``'s now,
    the rule the single-device sweep shares; the widths themselves are
    what ``_quantize_local`` and ``min(eff, local_n)`` gave."""

    @pytest.mark.parametrize("n,ndev,cap,want", [
        (N, 8, 1 << 10, (128, 128)),       # 97 a shard
        (757, 8, 1 << 10, (128, 128)),     # 95
        (300, 8, 1 << 10, (64, 64)),       # 38
        (300 + 757, 8, 1 << 10, (256, 256)),
        (203, 8, 1 << 10, (32, 32)),       # 26
        (130, 8, 1 << 8, (32, 32)),        # 17
        (257, 8, 1 << 9, (64, 64)),        # 33
        (1, 8, 1 << 10, (1, 1)),
        (8 * 1024, 8, 1 << 10, (1024, 1024)),
        (8 * 3000, 8, 1 << 10, (3000, 1024)),   # wide shards: exact
        (1 << 23, 4, 1 << 21, (1 << 21, 1 << 21)),  # crush-pod-sweep-8m
        (100_000_000, 8, 1 << 21, (12_500_000, 1 << 21)),
    ])
    def test_widths_are_what_they_were(self, n, ndev, cap, want):
        from ceph_tpu.crush.sharded_sweep import _shard_widths
        m, rid = _hier(4, 2)
        mp = Mapper(m, block=cap)
        assert mp.effective_block(rid, 3) == cap
        local = max(1, -(-n // ndev))
        assert _shard_widths(mp, rid, 3, local) == want
        # the arithmetic that stood in sharded_sweep before the move
        old = 1 << max(0, local - 1).bit_length() if local <= cap \
            else local
        assert want == (old, min(cap, old))

    def test_effective_block_is_the_cap(self, monkeypatch):
        """Narrower blocks for short sweeps leave ``effective_block``
        the widest one: the explicit or auto block on the XLA path, at
        least 2^21 on the kernel path."""
        m, rid = _hier(4, 2)
        assert Mapper(m, block=1 << 10).effective_block(rid, 3) == 1 << 10
        auto = Mapper(m)
        assert auto.effective_block(rid, 3) == auto.block >= 1 << 14
        monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "interpret")
        mk = Mapper(m, block=1 << 10)
        assert mk._kernel_body(rid, 3) is not None
        assert mk.effective_block(rid, 3) == 1 << 21


class TestKernelPath:
    """The fused kernel (interpret mode) through the sharded path —
    including lanes the kernel flags to its bit-exact XLA fallback."""

    @pytest.fixture(autouse=True)
    def _interpret_mode(self, monkeypatch):
        monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "interpret")

    def test_ambiguity_flagged_lanes_bit_exact(self, mesh,
                                               monkeypatch):
        """Blown-up margin: EVERY lane flags to the kernel's XLA
        fallback inside every shard — the sharded result must still
        equal the scalar spec (the acceptance criterion's
        ambiguity-lane clause). Continuous weights, so the flagging
        runs the round-10 two-phase choose."""
        from ceph_tpu.crush import pallas_mapper as pm
        monkeypatch.setattr(pm, "MARGIN_ABS", 1e30)
        m, root = builder.build_flat(
            8, weights=[WEIGHT_ONE + 991 * i for i in range(8)])
        rid = builder.add_simple_rule(m, root, builder.TYPE_OSD)
        mp = Mapper(m, block=1 << 8)
        assert mp._kernel_body(rid, 3) is not None
        assert 0 in mp._kernel_plan(rid).kmax    # continuous level
        xs = np.arange(130, dtype=np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        _assert_rows_match_ref(m, rid, got, xs, 3)

    @pytest.mark.slow
    def test_kernel_sharded_bit_exact(self, mesh):
        """Unflagged kernel lanes through the sharded path vs the
        single-device kernel engine (deep variant; tier-1 covers the
        kernel+sharded combination via the ambiguity test above)."""
        m, rid = _hier(4, 4)
        mp = Mapper(m, block=1 << 9)
        assert mp._kernel_body(rid, 3) is not None
        xs = np.arange(257, dtype=np.uint32)
        got = np.asarray(sharded_map_pgs(mesh, mp, rid, xs, 3))
        mx = Mapper(m, block=1 << 9)
        want = np.asarray(mx.map_pgs(rid, xs, 3))
        assert (got == want).all()
        _assert_rows_match_ref(m, rid, got[:16], xs[:16], 3)


class TestWiring:
    def test_mapper_mesh_option(self, mesh, hier):
        """Mapper(mesh=...) routes big batches through the sharded
        path (recorded in last_map_path), small ones stay local."""
        m, rid, mx, want = hier
        mp = Mapper(m, block=1 << 10, mesh=mesh, mesh_min_batch=128)
        xs = np.arange(N, dtype=np.uint32)
        got = np.asarray(mp.map_pgs(rid, xs, 3))
        assert mp.last_map_path == "xla+sharded"
        assert (got == want).all()
        small = np.asarray(mp.map_pgs(rid, xs[:16], 3))
        assert mp.last_map_path == "xla"
        assert (small == want[:16]).all()
        c, b = mp.sweep(rid, 0, 757, 3)
        assert mp.last_map_path == "xla+sharded"
        c1, b1 = mx.sweep(rid, 0, 757, 3)
        assert (np.asarray(c) == np.asarray(c1)).all()
        assert int(b) == int(b1)

    def test_osdmap_mapping_sharded_full_sweep(self, mesh):
        """The round-10 satellite: a crush-topology change forces the
        full-sweep fallback; with a mesh attached it runs sharded and
        bumps remap_sharded_sweeps (the prometheus counter's source).
        The resulting table must equal a mesh-less rebuild."""
        from ceph_tpu.bench import osdmaptool
        from ceph_tpu.osd.osdmap import PERF
        from ceph_tpu.osd.osdmap_mapping import OSDMapMapping

        m = osdmaptool.create_simple(32, 256, 3, erasure=False)
        before = PERF.dump()["remap_sharded_sweeps"]
        mm = OSDMapMapping(m, mesh=mesh, mesh_min_batch=1)
        assert mm.last_sharded_sweeps > 0
        assert mm.last_full_sweep_pools > 0
        # crush topology edit -> full-sweep fallback, sharded again
        from ceph_tpu.osd.osdmap import Incremental
        m.crush.buckets[-1].weights[0] += 7        # in-place edit
        m.crush_version += 1
        m.apply_incremental(Incremental(epoch=m.epoch + 1))
        mm.update(m)
        assert mm.last_sharded_sweeps > 0
        assert PERF.dump()["remap_sharded_sweeps"] > before
        # bit-identical vs a from-scratch mesh-less table
        plain = OSDMapMapping(m)
        for pid in m.pools:
            assert (mm._pools[pid].up == plain._pools[pid].up).all()
            assert (mm._pools[pid].acting
                    == plain._pools[pid].acting).all()

    def test_crush_sweep_span(self, mesh):
        """Tracing satellite: bulk full sweeps emit a crush_sweep span
        tagged n_pgs/path/n_devices through the attached Tracer."""
        from ceph_tpu.bench import osdmaptool
        from ceph_tpu.osd.osdmap_mapping import OSDMapMapping
        from ceph_tpu.utils.tracing import Tracer

        tracer = Tracer("osd.test",
                        {"trace_sampling_rate": 1.0,
                         "trace_slow_keep_s": 30.0})
        m = osdmaptool.create_simple(16, 64, 3, erasure=False)
        OSDMapMapping(m, mesh=mesh, mesh_min_batch=1, tracer=tracer)
        spans = [s for s in tracer.dump()["spans"]
                 if s["name"] == "crush_sweep"]
        assert spans, "no crush_sweep span recorded"
        tags = spans[-1]["tags"]
        assert tags["n_pgs"] == 64
        assert tags["n_devices"] == 8
        assert tags["path"].endswith("+sharded")

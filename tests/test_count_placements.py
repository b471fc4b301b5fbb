"""The sweep's per-device histogram (crush/mapper.py::_count_placements).

Both sweep steps (``_compiled_sweep`` and the sharded twin) count a
block's placements through this one helper. It has to be exact for
every caller's shapes -- small XLA-path blocks, the kernel path's 2^21
lanes, any ``device_counts_size`` -- and it must stay conflict-free: a
scatter-add over colliding ids serialises on the TPU (526 of a 599 ms
sweep before it was replaced), so the jaxprs of both steps are pinned
to hold none.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ceph_tpu.crush import builder
from ceph_tpu.crush.mapper import (Mapper, _compiled_sweep,
                                   _count_placements)
from ceph_tpu.crush.sharded_sweep import (_compiled_sharded_sweep,
                                          _fn_body)
from ceph_tpu.parallel import local_mesh

RMAX = 3


def _ids(dist: str, block: int, nbins: int, rng) -> np.ndarray:
    """(block, RMAX) int32 ids as a sweep step hands them over: device
    ids below nbins - 1, masked lanes in the padding bin nbins - 1."""
    if dist == "uniform":
        return rng.integers(0, nbins - 1, size=(block, RMAX),
                            dtype=np.int32)
    if dist == "one_bin":
        return np.full((block, RMAX), (nbins - 1) // 2, dtype=np.int32)
    if dist == "all_padding":
        return np.full((block, RMAX), nbins - 1, dtype=np.int32)
    assert dist == "short_tail"
    ids = rng.integers(0, nbins - 1, size=(block, RMAX), dtype=np.int32)
    ids[37:] = nbins - 1                # remaining = 37 live lanes
    return ids


CASES = [(block, nbins) for block in (1 << 9, 1 << 12, 1 << 16)
         for nbins in (257, 10_241)] + [(1 << 9, 100_001)]


@pytest.mark.parametrize("dist", ["uniform", "one_bin", "all_padding",
                                  "short_tail"])
@pytest.mark.parametrize("block,nbins", CASES)
def test_counts_equal_bincount(block, nbins, dist):
    ids = _ids(dist, block, nbins, np.random.default_rng(block + nbins))
    with jax.enable_x64(True):          # as every sweep step runs
        got = jax.jit(_count_placements, static_argnums=1)(
            jnp.asarray(ids), nbins)
    assert got.dtype == jnp.int32 and got.shape == (nbins,)
    want = np.bincount(ids.reshape(-1), minlength=nbins)
    assert np.array_equal(np.asarray(got), want)


def test_odd_shapes_and_out_of_range_ids():
    """Any shape counts (a Mapper's block is the caller's number, not a
    power of two), and an id outside [0, nbins) counts nowhere."""
    rng = np.random.default_rng(5)
    ids = rng.integers(-3, 300, size=(1000, 5), dtype=np.int32)
    got = np.asarray(_count_placements(jnp.asarray(ids), 257))
    keep = ids[(ids >= 0) & (ids < 257)]
    assert np.array_equal(got, np.bincount(keep, minlength=257))


@pytest.mark.parametrize("cols", [8, 9, 11, 16, 20])
def test_a_wide_result_counts_every_column(cols):
    """An 11-wide EC result is counted eight columns at a time (one
    pass lost a vector row on the chip): every column still counts."""
    rng = np.random.default_rng(cols)
    ids = rng.integers(-1, 600, size=(4096, cols), dtype=np.int32)
    got = np.asarray(_count_placements(jnp.asarray(ids), 513))
    keep = ids[(ids >= 0) & (ids < 513)]
    assert np.array_equal(got, np.bincount(keep, minlength=513))
    assert got.sum() == len(keep)


@pytest.fixture(scope="module")
def swept():
    m, root = builder.build_hierarchy(8, 4, n_racks=2)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST)
    return Mapper(m, block=1 << 9), rid


def _assert_conflict_free(jaxpr):
    text = str(jaxpr)                   # nested jaxprs print inline
    assert "dot_general" in text        # the counting is in there
    assert "scatter-add" not in text and "scatter_add" not in text


def test_sweep_step_holds_no_scatter_add(swept):
    mp, rid = swept
    fn_body, _ = _fn_body(mp, rid, RMAX)
    nd = mp.packed.max_devices
    step = _compiled_sweep(fn_body, False, nd, mp.block, RMAX)
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(step)(
            mp.arrays, jnp.zeros(nd + 1, dtype=jnp.int64), jnp.int64(0),
            jnp.uint32(0), jnp.int64(mp.block))
    _assert_conflict_free(jaxpr)


def test_sharded_step_holds_no_scatter_add(swept):
    mp, rid = swept
    fn_body, _ = _fn_body(mp, rid, RMAX)
    step = _compiled_sharded_sweep(
        fn_body, mp.packed.max_devices, local_mesh(), mp.block,
        mp.block, RMAX)
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(step)(mp.arrays, jnp.uint32(0),
                                     jnp.int64(8 * mp.block))
    _assert_conflict_free(jaxpr)

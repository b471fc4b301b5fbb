"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED v5e
(nothing runs, nothing is timed). Interpret mode cannot see what these
see: scoped-VMEM refusals, unaligned slices, ops Mosaic will not lower.
The kernels of the main path compile here at the shapes the OSD, the
benchmarks and ``chip_smoke.py`` launch them with, so a change that the
chip would refuse fails a test instead of a launch.

All chip compiles live in THIS file: only one process may load libtpu,
the topology is described inside a module fixture (never at import),
and the xdist worker that gets this file keeps the library until it
exits. The persistent compile cache is off around them — a compile for
a described chip cannot be read back without one.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ceph_tpu.gf import pallas_kernels as pk

OBJECT = 4 << 20            # rados bench / ceph_erasure_code_benchmark size


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _ec(k, m):
    from ceph_tpu.ec.jax_plugin import ErasureCodeJax
    return ErasureCodeJax(f"k={k} m={m} technique=reed_sol_van "
                          f"backend=pallas")


def _compile_ec(kern, batch, C, sharding):
    """Compile the fused kernel for ``kern``'s plan at (batch, rows, C)
    exactly as _MatrixKernel.apply_batch launches it."""
    rows_out, rows_in = kern.coeffs.shape
    assert pk.pallas_ok(C, rows_in, rows_out)
    plan = pk.EncodePlan(*[
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        for a in kern.plan])
    data = jax.ShapeDtypeStruct((batch, rows_in, C), jnp.uint8,
                                sharding=sharding)
    compiled = pk.encode_batch_planned.lower(plan, data).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (2, 2), (8, 4), (16, 4)])
def test_ec_encode_compiles_at_4mib_objects(one_chip, k, m):
    """8+3 is the headline profile, 4+2 tracked config #1, 2+2
    upstream's default profile; 8+4 and 16+4 sit on the other edges of
    the tile model. PR 22: the chip's compiler refused 4+2, 2+2 and
    8+4 under the old ``k * tile <= 1 MiB`` model."""
    ec = _ec(k, m)
    _compile_ec(ec._encode_kernel, 16, ec.get_chunk_size(OBJECT), one_chip)


def test_ec_decode_two_erasures_compiles(one_chip):
    ec = _ec(8, 3)
    erased = (0, 1)
    avail = tuple(c for c in range(11) if c not in erased)[:8]
    _compile_ec(ec._decode_kernel(avail, erased), 16,
                ec.get_chunk_size(OBJECT), one_chip)


@pytest.mark.parametrize("k,m,C,tile", [
    (8, 3, 512 << 10, 128 << 10),
    (8, 4, 512 << 10, 64 << 10),
    (4, 2, 1 << 20, 32 << 10),
    (2, 2, 2 << 20, 32 << 10),
    (10, 4, 128 << 10, 64 << 10),
    (8, 3, 4096, 0),                 # the OSD's stripe_unit: never fused
    (8, 16, 512 << 10, 0),           # nothing fits: stays on XLA
])
def test_tile_model(k, m, C, tile):
    """No compile: the tile the model picks at the shapes above."""
    assert pk._pick_tile(k, m, C) == tile
    assert pk.pallas_ok(C, k, m) == bool(tile)


@pytest.mark.parametrize("variant", ["uniform", "choose_args"])
def test_crush_kernel_compiles_at_10k_osds(one_chip, variant):
    """The fused CRUSH kernel on the canonical 10,240-OSD map, rule 0,
    numrep 3, under the caller's enable_x64 as Mapper launches it:
    the uniform plan and the continuous choose_args plan
    (_choose_level_cont), whose VMEM model only a real compile tests."""
    from ceph_tpu.bench import crush_sweep
    from ceph_tpu.crush import pallas_mapper as pm
    from ceph_tpu.crush.mapper import Mapper

    if variant == "uniform":
        mapper = Mapper(crush_sweep.canonical_map(10240))
    else:
        mapper = Mapper(crush_sweep.choose_args_map(10240), choose_args=0)
    plan = mapper._kernel_plan(0)
    assert plan is not None, "build_plan declined the canonical map"
    lanes, _fold, _groups = pm.kernel_geometry(plan, 3 + pm.SPEC_EXTRA)
    xs = jax.ShapeDtypeStruct((4 * lanes,), jnp.int32, sharding=one_chip)
    with jax.enable_x64(True):
        compiled = pm._run_kernel.lower(plan, xs, 3).compile()
    assert "tpu_custom_call" in compiled.as_text()


HYBRID_RULE = """rule mixed_replicated_rule {
\tid 1
\ttype replicated
\tstep take root class ssd
\tstep chooseleaf firstn 1 type host
\tstep emit
\tstep take root class hdd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
"""


def test_crush_kernel_compiles_a_plan_a_take_block(one_chip):
    """The docs' SSD-primary rule on the 10,240-OSD map with 12 hdd and
    4 ssd OSDs a host: one plan a take/emit block, the ssd one for one
    replica over hosts of 4, the hdd one for three over hosts of 12,
    each compiled at the replica count the sweep launches it with."""
    from ceph_tpu.bench import crushtool
    from ceph_tpu.crush import pallas_mapper as pm
    from ceph_tpu.crush.compiler import compile_crushmap, decompile_crushmap
    from ceph_tpu.crush.tensors import pack_map

    built = crushtool.build_map(crushtool.parse_args(
        ["--build", "--num-osds", "10240", "--hosts", "640", "--racks",
         "20", "--alg", "straw2"]))
    lines = [ln + (" class " + ("hdd" if int(ln.split()[1]) % 16 < 12
                                 else "ssd")
                   if ln.startswith("device ") else "")
             for ln in decompile_crushmap(built).splitlines()]
    m = compile_crushmap("\n".join(lines) + "\n" + HYBRID_RULE)
    plans = pm.build_plan(m, pack_map(m), 1)
    assert isinstance(plans, tuple) and len(plans) == 2
    for plan, numrep in zip(plans, (1, 3)):
        lanes, _fold, _groups = pm.kernel_geometry(plan,
                                                   numrep + pm.SPEC_EXTRA)
        xs = jax.ShapeDtypeStruct((4 * lanes,), jnp.int32,
                                  sharding=one_chip)
        with jax.enable_x64(True):
            compiled = pm._run_kernel.lower(plan, xs, numrep).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_count_placements_compiles_at_kernel_block(one_chip):
    """The sweep step's histogram at the kernel path's block (2^21
    lanes x 3, the 10,240-OSD map's 10,241 bins), fed as the kernel
    hands its result over (``leaves.T``): no scatter, the one-hots made
    inside the matmul's fusion, and no row-major copy of the (block, 3)
    ids (that pads 3 to 128 lanes: 1 GiB of temporaries)."""
    from ceph_tpu.crush.mapper import _count_placements

    def count(leaves):
        return _count_placements(leaves.T, 10_241)

    leaves = jax.ShapeDtypeStruct((3, 1 << 21), jnp.int32,
                                  sharding=one_chip)
    with jax.enable_x64(True):
        compiled = jax.jit(count).lower(leaves).compile()
    text = compiled.as_text()
    assert "convolution(" in text and "scatter(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20



def test_general_draw_ln_fuses_its_one_hots(one_chip):
    """The XLA general draw's crush_ln (``mapper._straw2_neg``) over the
    kernel recompute's buffer, 8,192 lanes x 32 slots: the one-hot
    compares are made inside the matmuls' fusions, so no (129 or 256,
    262,144) one-hot reaches HBM, and no element gather is left."""
    from ceph_tpu.crush import pallas_mapper as pm
    from ceph_tpu.crush.mapper import _straw2_neg

    rhlh, ll = pm._ln_plane_tables()
    arrs = {k: jax.ShapeDtypeStruct(t.shape, jnp.float32, sharding=one_chip)
            for k, t in (("ln_rhlh", rhlh), ("ln_ll", ll))}
    u = jax.ShapeDtypeStruct((8192, 32), jnp.int32, sharding=one_chip)
    with jax.enable_x64(True):
        compiled = jax.jit(_straw2_neg).lower(arrs, u).compile()
    text = compiled.as_text()
    assert text.count("convolution(") == 2 and " gather(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20

"""The plain references against the program's own, at small sizes: they
share no code, so agreement here is two witnesses."""

import numpy as np
import pytest

from reference import crush_ref, rs_ref

MAP_10K = {"osds": 10240, "hosts": 640, "racks": 20, "alg": "straw2",
           "failure_domain": "rack", "osd_weight": 65536}
SMALL = {"osds": 96, "hosts": 12, "racks": 4, "alg": "straw2",
         "failure_domain": "rack", "osd_weight": 65536}


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (2, 1), (10, 4)])
def test_coding_matrix_is_jerasures(k, m):
    from ceph_tpu.ec import matrix
    assert np.array_equal(rs_ref.coding_matrix(k, m),
                          matrix.reed_sol_van(k, m))


def test_first_parity_is_xor():
    assert (rs_ref.coding_matrix(8, 3)[0] == 1).all()
    assert (rs_ref.coding_matrix(8, 3)[:, 0] == 1).all()


def test_shards_roundtrip_and_reconstruct():
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, 3 * 8 * 4096 + 100, dtype=np.uint8).tobytes()
    sh = rs_ref.shards(data, 8, 3, 4096)
    assert len(sh) == 11 and {len(s) for s in sh} == {4 * 4096}
    assert rs_ref.assemble(sh[:8], 8, 4096, len(data)) == data
    for lost in (0, 5, 9):
        have = {i: s for i, s in enumerate(sh) if i != lost and i != 1}
        assert rs_ref.reconstruct(have, lost, 8, 3) == sh[lost]


def test_shards_are_the_plugins():
    from ceph_tpu.ec import factory
    ec = factory("plugin=jax technique=reed_sol_van k=8 m=3")
    rng = np.random.default_rng(4)
    stripes = rng.integers(0, 256, (5, 8, 4096), dtype=np.uint8)
    parity = np.asarray(ec.encode_batch_reference(stripes))
    data = stripes.reshape(-1).tobytes()
    sh = rs_ref.shards(data, 8, 3, 4096)
    for i in range(3):
        assert sh[8 + i] == parity[:, i, :].tobytes()


def test_crush_ln_table_is_the_programs():
    from ceph_tpu.crush.ln_table import crush_ln
    want = np.asarray(crush_ln(np.arange(65536))) - (1 << 48)
    assert np.array_equal(crush_ref.ln16("exact"), want)


@pytest.mark.parametrize("desc", [SMALL, MAP_10K], ids=["small", "10k"])
def test_batch_is_scalar_is_mapper_ref(desc):
    from ceph_tpu.bench import crushtool
    from ceph_tpu.crush import mapper_ref
    m = crush_ref.build_map(desc)
    args = crushtool.parse_args(
        ["--build", "--num-osds", str(desc["osds"]), "--hosts",
         str(desc["hosts"]), "--racks", str(desc["racks"])])
    pm = crushtool.build_map(args)
    xs = np.random.default_rng(7).integers(0, 2 ** 32, 200, dtype=np.uint64)
    batch = crush_ref.map_batch(m, xs, 3)
    for x, row in zip(xs, batch.tolist()):
        assert crush_ref.do_rule(m, int(x), 3) == row
        assert mapper_ref.do_rule(pm, 0, int(x), 3) == row


def test_collisions_retry_like_the_scalar():
    # 4 racks and 4 replicas asked: many collisions, some unfilled
    m = crush_ref.build_map(SMALL)
    xs = np.arange(3000, dtype=np.uint64)
    batch = crush_ref.map_batch(m, xs, 4)
    for x in (0, 17, 999, 2999):
        row = crush_ref.do_rule(m, x, 4)
        row += [crush_ref.ITEM_NONE] * (4 - len(row))
        assert row == batch[x].tolist()


def test_sweep_counts_and_the_float32_control():
    m = crush_ref.build_map(MAP_10K)
    counts, bad = crush_ref.sweep_counts(m, 1 << 20, 1 << 15, 3)
    assert counts.sum() == 3 * (1 << 15) and bad == 0
    control, _ = crush_ref.sweep_counts(m, 1 << 20, 1 << 15, 3, "float32")
    assert np.abs(counts - control).sum() > 0

"""GF(2^8) field and kernel tests.

Pattern mirrors the reference's EC unit tests: known-answer + algebraic
property checks (ref: src/test/erasure-code/TestErasureCode.cc style).
"""

import numpy as np
import pytest

from ceph_tpu.gf import (
    coeff_bitmatrix, expand_bitmatrix, gf_div, gf_inv, gf_matinv_np,
    gf_matmul_np, gf_matmul_bitplanes, gf_matmul_bytes, gf_matmul_lut,
    gf_mul, gf_mul_np, gf_pow, nibble_tables, pack_bits, unpack_bits,
)
from ceph_tpu.gf.tables import mul_table


class TestField:
    def test_known_products(self):
        # Hand-checked products under poly 0x11d.
        assert gf_mul(0, 5) == 0
        assert gf_mul(1, 5) == 5
        assert gf_mul(2, 128) == 0x11D ^ 0x100  # alpha * alpha^7 overflows
        assert gf_mul(3, 7) == 9  # (x+1)(x^2+x+1) = x^3+1
        # Commutativity + associativity on a sample.
        for a in (3, 77, 200, 255):
            for b in (9, 101, 254):
                assert gf_mul(a, b) == gf_mul(b, a)
                assert gf_mul(a, gf_mul(b, 13)) == gf_mul(gf_mul(a, b), 13)

    def test_distributive(self):
        for a in (5, 130, 251):
            for b in (17, 68):
                for c in (33, 240):
                    assert gf_mul(a, b ^ c) == gf_mul(a, b) ^ gf_mul(a, c)

    def test_inverse(self):
        for a in range(1, 256):
            assert gf_mul(a, gf_inv(a)) == 1
            assert gf_div(a, a) == 1

    def test_pow(self):
        assert gf_pow(2, 0) == 1
        x = 1
        for n in range(1, 20):
            x = gf_mul(x, 2)
            assert gf_pow(2, n) == x

    def test_mul_table_symmetric(self):
        t = mul_table()
        assert np.array_equal(t, t.T)
        assert np.array_equal(t[1], np.arange(256, dtype=np.uint8))


class TestBitmatrix:
    def test_coeff_bitmatrix_is_multiplication(self, rng):
        for c in (0, 1, 2, 3, 0x1D, 137, 255):
            M = coeff_bitmatrix(c)
            for x in rng.integers(0, 256, size=16):
                bits = (int(x) >> np.arange(8)) & 1
                ybits = M @ bits % 2
                y = int((ybits << np.arange(8)).sum())
                assert y == gf_mul(c, int(x)), (c, x)

    def test_expand_matches_blocks(self):
        m = np.array([[3, 7], [1, 255]], dtype=np.uint8)
        B = expand_bitmatrix(m)
        assert B.shape == (16, 16)
        assert np.array_equal(B[0:8, 8:16], coeff_bitmatrix(7))
        assert np.array_equal(B[8:16, 0:8], coeff_bitmatrix(1))


class TestMatinv:
    def test_roundtrip(self, rng):
        for n in (1, 2, 4, 8):
            while True:
                m = rng.integers(0, 256, size=(n, n)).astype(np.uint8)
                try:
                    inv = gf_matinv_np(m)
                    break
                except ValueError:
                    continue
            eye = gf_matmul_np(m, inv)
            assert np.array_equal(eye, np.eye(n, dtype=np.uint8))

    def test_singular_raises(self):
        with pytest.raises(ValueError):
            gf_matinv_np(np.zeros((3, 3), dtype=np.uint8))


class TestKernels:
    @pytest.fixture
    def case(self, rng):
        m = rng.integers(0, 256, size=(3, 8)).astype(np.uint8)
        data = rng.integers(0, 256, size=(8, 512)).astype(np.uint8)
        expect = gf_matmul_np(m, data)
        return m, data, expect

    def test_unpack_pack_roundtrip(self, rng):
        data = rng.integers(0, 256, size=(4, 64)).astype(np.uint8)
        assert np.array_equal(np.asarray(pack_bits(unpack_bits(data))), data)

    def test_bitplanes_matches_oracle(self, case):
        m, data, expect = case
        B = expand_bitmatrix(m).astype(np.int8)
        got = np.asarray(gf_matmul_bitplanes(B, data))
        assert np.array_equal(got, expect)

    def test_lut_matches_oracle(self, case):
        m, data, expect = case
        lo, hi = nibble_tables(m)
        got = np.asarray(gf_matmul_lut(lo, hi, data))
        assert np.array_equal(got, expect)

    def test_bytes_matches_oracle(self, case):
        m, data, expect = case
        got = np.asarray(gf_matmul_bytes(m, data))
        assert np.array_equal(got, expect)


class TestPallasKernel:
    """The fused pallas encode must be byte-exact vs the independent
    numpy GF oracle and the XLA bitmatmul path. Runs in interpret mode
    on CPU; the same code path runs compiled on TPU (benchmarked by
    bench.py, measured ~1.5x the XLA kernel on v5e)."""

    def _check(self, rng, k, m, B, C):
        from ceph_tpu.gf import pallas_kernels as pk

        mat = rng.integers(0, 256, size=(m, k)).astype(np.uint8)
        data = rng.integers(0, 256, size=(B, k, C)).astype(np.uint8)
        bm = expand_bitmatrix(mat)
        got = np.asarray(pk.encode_batch_planned(
            pk.make_plan(bm), np.asarray(data), interpret=True))
        expect = np.stack([gf_matmul_np(mat, d) for d in data])
        assert np.array_equal(got, expect), (k, m, B, C)

    def test_k8m3_tile_aligned(self, rng):
        from ceph_tpu.gf import pallas_kernels as pk
        self._check(rng, 8, 3, 2, pk.TILE_L)

    def test_multi_tile_and_geometries(self, rng):
        from ceph_tpu.gf import pallas_kernels as pk
        self._check(rng, 4, 2, 1, 2 * pk.TILE_L)
        self._check(rng, 10, 4, 2, pk.TILE_L)

    def test_plan_permutation(self, rng):
        from ceph_tpu.gf import pallas_kernels as pk

        mat = rng.integers(0, 256, size=(3, 8)).astype(np.uint8)
        bm = expand_bitmatrix(mat)
        plan = pk.make_plan(bm)
        bmm = np.asarray(plan.bm_bitmajor)
        k = 8
        for b in range(8):
            for i in range(k):
                assert np.array_equal(bmm[:, b * k + i], bm[:, 8 * i + b])

    def test_pallas_ok_gating(self):
        from ceph_tpu.gf import pallas_kernels as pk

        assert pk.pallas_ok(pk.TILE_L, 8, 3)
        assert pk.pallas_ok(4 * pk.TILE_L, 8, 3)
        assert not pk.pallas_ok(pk.TILE_L + 1, 8, 3)
        assert not pk.pallas_ok(0, 8, 3)


class TestPallasPlugin:
    """backend=pallas through the ErasureCodeJax plugin surface."""

    def test_encode_batch_matches_bitmatmul(self, rng):
        from ceph_tpu.ec.jax_plugin import ErasureCodeJax
        from ceph_tpu.gf import pallas_kernels as pk

        prof = "plugin=jax technique=reed_sol_van k=8 m=3"
        pall = ErasureCodeJax(prof + " backend=pallas")
        base = ErasureCodeJax(prof + " backend=bitmatmul")
        data = rng.integers(0, 256, size=(2, 8, pk.TILE_L)).astype(np.uint8)
        got = np.asarray(pall.encode_batch(np.asarray(data)))
        expect = np.asarray(base.encode_batch(np.asarray(data)))
        assert np.array_equal(got, expect)

    def test_unaligned_falls_back(self, rng):
        from ceph_tpu.ec.jax_plugin import ErasureCodeJax

        pall = ErasureCodeJax(
            "plugin=jax technique=reed_sol_van k=4 m=2 backend=pallas")
        base = ErasureCodeJax(
            "plugin=jax technique=reed_sol_van k=4 m=2 backend=bitmatmul")
        data = rng.integers(0, 256, size=(3, 4, 4096)).astype(np.uint8)
        got = np.asarray(pall.encode_batch(np.asarray(data)))
        expect = np.asarray(base.encode_batch(np.asarray(data)))
        assert np.array_equal(got, expect)

    def test_decode_roundtrip_pallas(self, rng):
        from ceph_tpu.ec.jax_plugin import ErasureCodeJax
        from ceph_tpu.gf import pallas_kernels as pk

        ec = ErasureCodeJax(
            "plugin=jax technique=reed_sol_van k=4 m=2 backend=pallas")
        data = rng.integers(0, 256, size=(4, pk.TILE_L)).astype(np.uint8)
        parity = np.asarray(ec.encode_chunks(data))
        chunks = {i: data[i] for i in range(4)} | {
            4 + j: parity[j] for j in range(2)}
        del chunks[0], chunks[5]
        out = ec.decode_chunks([0], chunks)
        assert np.array_equal(out[0], data[0])

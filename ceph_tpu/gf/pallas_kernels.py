"""Pallas TPU kernels for the GF(2^8) bit-plane encode.

The XLA `bitmatmul` path (gf.ops.gf_matmul_bitplanes) materializes the
(8k, L) int8 bit-plane expansion in HBM — 8x the payload in traffic —
before the MXU contraction, which caps encode throughput far below the
payload roofline. These kernels fuse unpack -> int8 matmul -> mod-2 ->
pack inside one VMEM tile, so HBM sees only the payload
(read k + write m chunks ≈ 1 + m/k bytes moved per byte encoded).

Design notes (round 4, all measured on a v5e with the interleaved
median-of-paired-slopes protocol; round-3 numbers in parentheses):

- **Mod-2 absorb — the `& 1` before the matmul is unnecessary.** The
  MXU only needs operands CONGRUENT to the bit mod 2: feeding the
  whole shifted byte `(data >> b)` wrapped to int8 keeps parity intact
  (the int8 wrap changes the value by a multiple of 256 — even; the
  int32 accumulator is exact at |acc| <= 8k * 128; the epilogue's
  `acc & 1` kills all junk). One full VPU pass gone.
- **Per-plane constant shifts** replace round 3's
  `concatenate([data]*8)` + broadcasted-iota variable shift: 8 (or 16,
  see below) immediate-shift ops on (k, T) int32, each cast straight
  to int8 — no (8k, T) int32 intermediate, no iota. (Shifting in the
  int8/uint8 domain does not lower in Mosaic — measured, compile
  error — so the shifts stay in native 32-bit lanes.)
- **Block-diagonal r=2 contraction.** The k=8 coding matmul is
  (24, 64) — it uses 9% of the 128x128 systolic array and streams one
  column per cycle anyway. Splitting the tile into two lane-halves and
  stacking their planes gives a (48, 128) @ (128, T/2) product: the
  full contraction depth at half the column count. Applied whenever
  2*8k <= 128.
- **Aligned pack rows.** The mod-2 + byte-pack epilogue is one bf16
  MXU matmul (weights 2^b <= 128 and pbits {0,1} are bf16-exact; the
  f32 accumulator is exact <= 255). The two half-results ride rows
  [0, m) and [8, 8+m) of a 16-row output so both final stores are
  sublane-tile-aligned — Mosaic crashes on an int8 lane-concat whose
  operand carries a vpad sublane offset (measured: the naive
  (3, h)+(3, h) concat), and rejoining the int32 acc halves instead
  costs a 3 MiB VMEM copy per tile (~0.35 ms/step at the bench shape).
- Stage attribution at the bench shape (64 x 8 x 512 KiB, 2.4 ms/step
  full): unpack shifts ~0.76 ms, main matmul ~0.66 ms, epilogue
  ~0.35 ms, HBM floor 0.43 ms — the stages mostly serialize, so the
  formulation is VPU/MXU-issue-bound, not bandwidth-bound. int4
  operands compile but run SLOWER (extra `& 1` + casts outweigh the
  MXU rate); int32 operands don't lower.
- Net: ~103 GiB/s encode at k=8,m=3 on 256 MiB steps (round 3:
  ~79 GiB/s same protocol; round-3's published 88 was a luckier
  platform window — see BASELINE.md).
- The batched entry point takes (B, k, C) stripes directly with a
  (B, C/tile) grid so callers never pay the (B,k,C) -> (k, B*C)
  transpose the XLA path needs. Both grid dims are `parallel`
  (independent output tiles).

The plan (permuted bitmatrix + block-diag operand + pack weights) is
built eagerly on the host (make_plan) because the permutation needs
concrete values; the jitted entry then treats the plan arrays as
ordinary operands.

ref: the role of ISA-L's ec_encode_data AVX512 kernels
(src/erasure-code/isa); the bit-plane formulation is SURVEY.md §7
step 1's MXU mapping.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Minimum lane-tile bytes per grid step (and the alignment callers must
# provide). encode_batch_planned picks the largest tile in
# [TILE_L, TILE_MAX] that divides C and whose scoped-VMEM allocation the
# chip's compiler accepts — measured on v5e: 128 KiB tiles beat 32 KiB
# by ~5%.
TILE_L = 1 << 15
TILE_MAX = 1 << 17
# Mosaic's scoped-VMEM limit for one kernel on v5e.
_VMEM_LIMIT = 16 << 20


class EncodePlan(NamedTuple):
    bm_bitmajor: jax.Array   # (8m, 8k) int8, cols permuted to b*k+i
    bm_op: jax.Array         # (r*8m, r*8k) int8 block-diag MXU operand
    packw: jax.Array         # (r*OFF, r*8m) bf16 aligned pack weights


def _scoped_bytes_per_lane(k: int, m: int) -> int:
    """Upper bound on the scoped VMEM Mosaic allocates per BYTE of lane
    tile for a (k rows in, m rows out) kernel.

    Measured, not derived: compile ``encode_batch_planned`` for a
    described v5e and read "Scoped allocation with size X and limit
    16.00M" at 64/128/256 KiB tiles (k in 1..16, 20, 24, 32; m in
    1..6, 8; the allocation is linear in the tile). It does NOT scale with k — the
    ``k * tile <= 1 MiB`` model this replaces let 4+2, 2+2, 2+1 and 8+4
    through at 128 KiB, which the chip's compiler refuses (interpret
    mode never allocates, so no test saw it). Four regimes, each the
    upper envelope of the measurements:

    - 2 <= k <= 4 (the stacked planes fill at most half the 128-deep
      contraction): 253..276 B/lane, nearly flat in m;
    - k <= 8 (block-diagonal r=2): ~36.5 B/lane per output row
      (m=3: 110, m=4: 146, m=8: 280) — the int32 accumulator and its
      bf16/f32 epilogue copies;
    - 9 <= k <= 16 (r=1, one 128-deep contraction tile): the same
      slope plus one more row's worth (m=3: 132, m=4: 163, m=8: 298),
      flat in k;
    - k > 16 (a second contraction tile): more per output row and a
      little per input row (k=20: m=3 150, m=8 370; k=32: m=3 166,
      m=8 386).

    tests/test_chip_compile.py compiles the shapes the OSD and the
    benchmarks use against the real compiler, so a libtpu that
    allocates more fails a test instead of a launch."""
    if 2 <= k <= 4:
        return 260 + 3 * m
    if k <= 8:
        return 38 * m
    if k <= 16:
        return 35 * (m + 1)
    return 35 * (m + 1) + 10 * m + 2 * (k - 16)


def _pick_tile(k: int, m: int, C: int) -> int:
    """Largest power-of-two tile in [TILE_L, TILE_MAX] dividing C whose
    modelled allocation fits the limit; 0 when none does (callers keep
    the XLA bitmatmul)."""
    per_lane = _scoped_bytes_per_lane(k, m)
    t = TILE_MAX
    while t >= TILE_L:
        if C % t == 0 and per_lane * t <= _VMEM_LIMIT:
            return t
        t //= 2
    return 0


def make_plan(bitmatrix: np.ndarray) -> EncodePlan:
    """Host-side constants for one coding bitmatrix (chunk-major rows
    8j+b / cols 8i+b', as produced by tables.expand_bitmatrix)."""
    bm = np.asarray(bitmatrix, dtype=np.int8)
    m8, k8 = bm.shape
    k, m = k8 // 8, m8 // 8
    bm_bitmajor = np.zeros_like(bm)
    for b in range(8):
        bm_bitmajor[:, b * k:(b + 1) * k] = bm[:, b::8]
    r = 2 if 2 * k8 <= 128 else 1
    bm_op = np.zeros((r * m8, r * k8), dtype=np.int8)
    for j in range(r):
        bm_op[j * m8:(j + 1) * m8, j * k8:(j + 1) * k8] = bm_bitmajor
    # Byte pack as one bf16 matmul: out[j] = sum_b (1<<b) * paritybit
    # [8j+b]; per lane-half j its m output rows start at j*OFF so every
    # final store slice is 8-sublane aligned.
    off = 8 * ((m + 7) // 8)
    pw = np.zeros((r * off, r * m8), dtype=np.float32)
    for j in range(r):
        for jj in range(m):
            for b in range(8):
                pw[j * off + jj, j * m8 + 8 * jj + b] = float(1 << b)
    return EncodePlan(jnp.asarray(bm_bitmajor),
                      jnp.asarray(bm_op),
                      jnp.asarray(pw).astype(jnp.bfloat16))


def _make_kernel(k: int, m: int, r: int, off: int):
    def kernel(bm_ref, pw_ref, data_ref, out_ref):
        data = data_ref[0].astype(jnp.int32)          # (k, T)
        T = data.shape[1]
        h = T // r
        if r == 2:
            halves = (data[:, :h], data[:, h:])
        else:
            halves = (data,)
        # constant-shift planes, no & 1 (mod-2 absorb: the int8 wrap of
        # data>>b differs from bit b by an even number; acc & 1 below
        # recovers the parity exactly — |acc| <= 8k*128 is int32-exact)
        planes = [(d >> b).astype(jnp.int8)
                  for d in halves for b in range(8)]
        bits = jnp.concatenate(planes, axis=0)        # (r*8k, h) int8
        acc = jax.lax.dot_general(
            bm_ref[...], bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)         # (r*8m, h)
        pbits = (acc & 1).astype(jnp.bfloat16)
        out = jax.lax.dot_general(
            pw_ref[...], pbits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (r*off, h)
        outi = out.astype(jnp.int32).astype(jnp.uint8)
        if r == 2:
            out_ref[0, :, 0:h] = outi[0:m]
            out_ref[0, :, h:2 * h] = outi[off:off + m]
        else:
            out_ref[0] = outi[0:m]
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_batch_planned(plan: EncodePlan, data: jax.Array,
                         interpret: bool = False) -> jax.Array:
    """plan x (B, k, C) uint8 -> (B, m, C) uint8 parity.

    (k, m, C) must pass pallas_ok; callers fall back to the XLA kernel
    otherwise."""
    m8, k8 = plan.bm_bitmajor.shape
    B, k, C = data.shape
    assert k8 == 8 * k, (plan.bm_bitmajor.shape, data.shape)
    m = m8 // 8
    r = plan.bm_op.shape[1] // k8
    off = plan.packw.shape[0] // r
    tile = _pick_tile(k, m, C)
    assert tile, f"no lane tile for rows {k}->{m}, C={C} (pallas_ok)"
    grid = (B, C // tile)
    params = {}
    if not interpret:
        # Output tiles are fully independent: both grid dims parallel
        # lets Mosaic overlap/pipeline across stripes and lane tiles.
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    return pl.pallas_call(
        _make_kernel(k, m, r, off),
        grid=grid,
        in_specs=[
            pl.BlockSpec(plan.bm_op.shape, lambda b, i: (0, 0)),
            pl.BlockSpec(plan.packw.shape, lambda b, i: (0, 0)),
            pl.BlockSpec((1, k, tile), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, m, tile), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, m, C), jnp.uint8),
        interpret=interpret,
        **params,
    )(plan.bm_op, plan.packw, data)


def gf_encode_batch_pallas(bitmatrix, data: jax.Array,
                           interpret: bool = False) -> jax.Array:
    """Eager convenience wrapper: chunk-major bitmatrix (host value) x
    (B, k, C) -> (B, m, C). Not callable under jit (plan needs values)."""
    return encode_batch_planned(make_plan(np.asarray(bitmatrix)), data,
                                interpret=interpret)


def gf_matmul_bitplanes_pallas(bitmatrix, data: jax.Array,
                               interpret: bool = False) -> jax.Array:
    """2-D wrapper: (8m, 8k) bitmatrix x (k, L) uint8 -> (m, L) uint8."""
    out = gf_encode_batch_pallas(bitmatrix, data[None], interpret=interpret)
    return out[0]


def pallas_ok(C: int, k: int, m: int) -> bool:
    """Fast-path eligibility: the lane/chunk length C is tile-aligned
    and the chip's compiler accepts a tile for a (k rows in, m rows
    out) matrix."""
    return C > 0 and _pick_tile(k, m, C) > 0

"""Fused Pallas TPU kernel for the CRUSH hot path.

Round 3 left CRUSH at 1.3M mappings/s single-chip: the XLA pipeline
pays HBM round-trips between every op of the hash->draw->argmax chain
and re-gathers bucket rows at every descent level. This kernel fuses
the ENTIRE rule execution — rjenkins hashing, the uniform-weight exact
straw2 draw with its ln-equality tie repair, bucket descent, chooseleaf
recursion, reweight rejection, and replica-slot resolution — into one
VMEM-resident Pallas program over PG-id lanes (ref: the role of
src/crush/mapper.c crush_do_rule + bucket_straw2_choose; SURVEY.md §3.2
hot loop, §7 step 4).

The enabling observation (new in round 4): with chooseleaf_stable=1 and
no choose_args, the descent for replica slot ``rep`` at retry ``ftotal``
depends ONLY on r = rep + ftotal (the `pos` argument matters only to
choose_args weight-sets, which gate the kernel off). So instead of the
XLA path's numrep x SPEC_TRIES speculative descents (which recompute
r=1,2 twice), the kernel computes ONE descent per candidate r in
[0, numrep + SPEC_EXTRA) and resolves all slots by scanning that shared
candidate table elementwise:

    slot s takes the first candidate r >= s that succeeded and does not
    collide with an earlier slot's item/leaf — exactly the scalar
    loop's sequence, because a candidate consumed by slot s' < s
    re-collides on its own item for slot s and is skipped.

Lanes where any slot exhausts all candidates (P ~ (collision rate)^
(SPEC_EXTRA+1) ~ 1e-8 on healthy maps) are flagged and recomputed
bit-exactly by the caller's masked XLA fallback — the while_loop costs
nothing when no lane is flagged.

Per-descent-level bucket row data (item ids for hashing, child row
indices, row size) is fetched with one-hot f32 MXU matmuls instead of
gathers (measured round 3: element gathers cost ~7-9ns each on this
platform; a (65, P)@(P, N) f32 matmul is ~0.1ns/lane). The ln-equality
tie predicate zg (ln_table.ln_gap_info) runs as an f32 MXU matmul over
its (256, 256) factorization. rjenkins runs in int32 with logical
shifts (Mosaic has no uint32 printf-exact guarantees; int32 two's-
complement add/sub/xor/shl wrap identically to C uint32, and
shift_right_logical supplies the unsigned right shift).

Mixed weights (round 5) ride a WEIGHT-CLASS decomposition of the
straw2 draw: group a bucket's slots by distinct weight (real buckets
mix 1-4 disk sizes). Within one class the round-3 uniform argument is
exact — the minimal truncated quotient q = (2^48 - crush_ln(u)) // w
is attained precisely by the ln-equality class of the maximal hash —
so the kernel computes ONE exact crush_ln per class (one-hot MXU
fetches of the 129-entry RH/LH and 256-entry LL tables; a byte ladder
for the 17x49-bit normalize product), then compares classes by the
f32 draw neg/w. Lanes whose top two class draws land within a margin
covering every f32 rounding and integer floor-tie possibility flag to
the caller's bit-exact XLA fallback (~1e-6 of lanes; gathered compactly
so the fallback is O(flagged), not O(block)). A single-weight-set
choose_args map is the same machinery with substituted weights —
position-independent, so the shared candidate table survives.

CONTINUOUS weights (round 6): buckets whose slots carry more than
MAX_CLASSES distinct weights — exactly what an upstream-style
balancer's choose_args weight-set produces (every slot perturbed a few
percent) — previously gated the whole map off the kernel and onto the
XLA general path (how much slower that is on a v5e is not measured;
what the continuous draw costs is: PERF.md §5-§6, PR 35). The class
decomposition degenerates
cleanly: treat EVERY slot as its own class. No within-class tie
argument (and hence no ln-gap license G) is needed at all, because a
one-slot class has no internal tie to break. Per-slot weights ride the
level table as two 15-bit halves, so any w < 2^30 is admissible — this
also covers few-class buckets whose weights exceed G.

TWO-PHASE pre-selection (round 10, this PR): round 6 ran the exact
fixed-point crush_ln ladder once PER SLOT, sequentially — a 3-level
choose_args map replayed ~(20+32+16) ladders per candidate r, which
both dominated runtime (each ladder is two one-hot MXU fetches plus a
byte-carry walk) and blew the compile up linearly in bucket width
(MAX_CONT_SLOTS existed to cap exactly that). The reformulation does
ONE fused pass instead:

- phase 1 scores ALL slots at once with a pure-f32 approximation of
  the draw: d~_s = 2^44*(16 - log2(u_s+1)) / w_s, the log2 evaluated
  by exact exponent/mantissa extraction plus a degree-7 polynomial
  (elementwise over the whole (S, N) plane — no per-slot unroll, no
  table fetch). The approximation's error against the exact crush_ln
  staircase is bounded by ERR_Z over the entire 16-bit domain
  (exhaustively verified, not estimated; the staircase's own
  quantization ~4.4e-5 dominates the polynomial's 8e-7);
- phase 2 runs the exact crush_ln ladder on just the TOP-2 phase-1
  candidates (two ladders per level, independent of S) and decides
  the winner by exact-f32 comparison under the usual
  MARGIN_ABS/MARGIN_REL envelope.

Soundness: a lane is flagged to the bit-exact XLA fallback when (a)
the top-2 exact draws land inside the margin (floor ties / f32
rounding — the round-6 envelope, unchanged), or (b) ANY third slot's
phase-1 score minus its proven error bound reaches the winner's exact
draw plus the margin — if the exact winner were outside the phase-1
top-2, its own lower bound would trip (b), so no unflagged lane can
misrank. Because (b) requires THREE draws inside a ~1e-4-relative
window, its rate is quadratically suppressed (~1e-6/choose measured),
the same order as the round-6 floor-tie flags.

LEVEL-MAJOR candidate batching (round 15, this PR): the descents for
the n_cand = numrep + SPEC_EXTRA candidate r values are mutually
independent until the final slot-resolution scan, and until now each
candidate replayed ALL l_total levels on its own — n_cand x l_total
one-hot fetches (the (2R, P) level-table load re-issued per
candidate) plus n_cand separate hash/choose passes per level, even
though the level-0 fetch is literally identical for every candidate
(all descents start at row 0). The kernel now advances all candidates
ONE LEVEL AT A TIME with the candidate axis folded into the lane
axis: per-candidate rows stack into (1, fold*N) operands so each
level runs ONE ``_fetch_level`` matmul with a fold-times-wider
one-hot and ONE batched choose pass with a per-column r vector — the
choose functions already broadcast (1, N)-shaped r over the slot
axis, so the per-column math is untouched and bit-exactness holds
lane for lane. Level 0 is hoisted outright: its stratum is the single
TAKE root (P == 1), so the "fetch" is one column broadcast shared by
every candidate and only the choose is candidate-batched. The fold
factor is VMEM-governed (``kernel_geometry``) and the accounting is
per PG, not per cell: streaming FLOPs are identical for every
geometry, so the win is the per-issue overhead ((2R, P) weight
loads, op issues) paid groups*l_total times per pg_lanes-wide cell —
minimized by spending the headroom between the LANES cell cap and
the VMEM model's raw lane budget on the candidate axis (a fold
carved out of the PG width alone can never beat the old kernel; the
geometry search proves its pick against fold=1, so the batched
kernel is never worse per PG and wins wherever VMEM headroom
exists). Result: the kernel body's dot_general count is O(l_total),
independent of numrep on headroom-rich maps (pinned by jaxpr
inspection in tests/test_pallas_mapper.py), and per-PG level passes
drop by n_cand*kernel_lanes/(groups*plan.lanes) — 5x for 3-replica
rules on the canonical-shape map, 2.5x on the VMEM-tighter 10k-OSD
bench map.

Eligibility (build_plan returns None otherwise; the caller keeps the
XLA path):
- modern tunables (chooseleaf_stable=1, no legacy local retries),
- rule shape TAKE root / CHOOSE[LEAF]_FIRSTN / EMIT, or several such
  take/emit blocks, each its own plan (``build_plan``),
- every bucket reachable from the root is straw2 and non-empty with at
  least one positive weight; weights above the class budget or the
  ln-gap license take the per-slot continuous draw (weights must fit
  two 15-bit halves, i.e. < 2^30 ~ 16Ki disks of weight 1.0, and the
  bucket at most MAX_CONT_SLOTS slots — the ladder unrolls per slot),
- uniform hierarchy depth (all root->target->device paths equal),
- choose_args: at most ONE weight set per bucket and no ids overrides,
- at most MAX_REWEIGHT non-full devices (is_out then runs as a
  compare-against-list; beyond that the XLA path's full devw table is
  the right tool).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.crush.types import (
    ALG_STRAW2, ITEM_NONE,
    OP_CHOOSELEAF_FIRSTN, OP_CHOOSE_FIRSTN, OP_EMIT, OP_NOOP, OP_TAKE,
    CrushMap, WEIGHT_ONE,
)

CRUSH_HASH_SEED = 1315423911

# perf triage only (results become WRONG): comma list of kernel stages
# to stub out — used to attribute kernel time between the zg tie
# matmul, the one-hot table fetch, and the rjenkins hashing on real
# hardware. Never set in production. ABLATE_STAGES is the complete
# documented set (tests/test_meta.py pins every `in _ABLATE` literal
# against it, so a new stage cannot ship undocumented):
# - nozg:    skip the ln-equality tie matmul (_zg_flag -> 0)
# - nofetch: skip the one-hot level fetch (broadcast column 0)
# - nohash:  replace rjenkins with a xor mix
ABLATE_STAGES = ("nozg", "nofetch", "nohash")
import os as _os
_ABLATE = set(filter(None, _os.environ.get(
    "CEPH_TPU_KERNEL_ABLATE", "").split(",")))

# Kernel-identity tag for devmon compile-warmth keys (round 15): the
# level-major candidate-batched kernel compiles a structurally
# different program than the round-4..14 candidate-major one, so
# `jit_compile` spans must distinguish a fresh batched-kernel compile
# from a stale plan re-trace. Bump on any kernel-body restructure.
KERNEL_VARIANT = "cbatch1"
SPEC_EXTRA = 2      # candidates beyond numrep; slot s scans
                    # numrep - s + SPEC_EXTRA candidates before the lane
                    # falls back (P(fallback) ~ collision^(SPEC_EXTRA+1))
MAX_REWEIGHT = 128  # largest non-full-device list the kernel carries
LANES = int(_os.environ.get("CEPH_TPU_KERNEL_LANES", "1024"))
                    # MAX PG lanes per grid cell; build_plan narrows
                    # per map so the working set fits scoped VMEM
MIN_LANES = 128     # one TPU lane tile; below this the kernel loses to
                    # the XLA path anyway, so build_plan declines
# Scoped-VMEM budget for one grid cell. The driver's libtpu enforces a
# 16 MiB kernel-vmem stack; Mosaic holds ~12 S-wide temps live through
# a choose (measured: the 10240-OSD FLAT map — root S=2560 — allocated
# 121.47M at 1024 lanes = 11.6 live (S,N) i32 arrays), plus the fetch's
# (2R, N) planes and (P, N) one-hot. Model both and keep 4 MiB headroom.
VMEM_BUDGET = 12 << 20
_LIVE_TEMPS = 12


MAX_CLASSES = 4     # distinct weights per bucket the class draw
                    # carries; real buckets mix 1-3 disk sizes. Beyond
                    # that (continuous balancer weight-sets) each slot
                    # becomes its own class: one exact crush_ln per
                    # slot instead of per class (see _choose_level_cont)
MAX_CONT_WEIGHT = 1 << 30   # continuous per-slot weights must split
                            # into two 15-bit table halves
MAX_CONT_SLOTS = 512  # round 10: the two-phase choose runs exactly TWO
                      # crush_ln ladders per level regardless of S (the
                      # round-6 per-slot unroll that capped this at 64
                      # is gone), so the cap now only bounds the level
                      # table's one-hot fetch (R = 4S+1 rows) and the
                      # (S, N) phase-1 temps — both linear in S and
                      # modeled by _plan_lanes, which narrows the lane
                      # count (and below MIN_LANES declines the plan)
                      # before this cap ever binds. Wider continuous
                      # buckets keep the XLA path.
# Weight-class draw comparison margin (see _choose_level_cls): lanes
# whose top two class draws land closer than ABS + best*REL are flagged
# to the bit-exact XLA fallback. REL covers the f32 rounding of
# neg (2^-24), w (2^-24) and the divide (2^-24) with ~4x safety; ABS
# covers integer floor ties (truncated quotients equal while rationals
# differ), which only matter when the quotients themselves are small —
# i.e. at heavy bucket weights (a 10k-OSD root draws at d ~ 2^19, so
# genuine floor ties run ~2^-19/pair and the flagged-lane rate scales
# with map weight; the fallback buffer in mapper._make_kernel_body
# scales with block width to absorb this).
MARGIN_ABS = 1.25
MARGIN_REL = 2.0 ** -21

# Two-phase continuous choose (round 10): phase-1 approximate scorer.
# _LOG2_POLY approximates log2(1 + t) on [0, 1) (degree-7 Chebyshev
# fit, max error 8.1e-7 in exact arithmetic); ERR_Z bounds
# |z_f32(u) - (2^48 - crush_ln(u))/2^44| over the ENTIRE 16-bit hash
# domain with the kernel's exact f32 op order — measured 4.43e-5
# (dominated by crush_ln's own index2 staircase quantization, not the
# polynomial), carried at 2.2x safety and asserted exhaustively by
# tests/test_pallas_mapper.py::test_approx_z_error_bound. REL_SLOP
# covers every relative-rounding contribution of the phase-1 score
# (w's f32 representation at w >= 2^24, the divide, fma/assoc
# differences between platforms) at ~16x safety.
_LOG2_POLY = (8.1214063e-07, 1.4426336, -0.72020257, 0.47172138,
              -0.32148254, 0.18865165, -0.075920321, 0.014598490)
ERR_Z = 1e-4
REL_SLOP = 2.0 ** -20


def _plan_lanes(sizes, rows, kmax) -> tuple[int, int]:
    """(lanes, vmem_lanes): the widest power-of-two PG cell width
    under both the LANES cap and the VMEM model, plus the RAW
    (uncapped, un-floored) VMEM lane budget — (0, 0) when even
    MIN_LANES does not fit (caller declines the plan).

    Since round 15 the VMEM model bounds the FOLDED width of a grid
    cell's intermediates — candidate-batched descent stacks fold
    candidates along the lane axis, so kernel_geometry spends the
    headroom between the LANES cap and vmem_lanes on the candidate
    axis first, and narrows the PG width only when that headroom is
    short. The per-folded-lane cost model is unchanged: the live
    temps per choose have the same shapes whether the lane is a PG or
    a (PG, candidate) column."""
    per_lane = 0
    for (S, P), R, K in zip(sizes, rows, kmax):
        extra = 0
        temps = _LIVE_TEMPS
        if K != 1:
            # class (K > 1) and continuous (K == 0) chooses add the
            # crush_ln machinery per lane: the (129, N) + (256, N) ln
            # one-hots plus ~35 (1, N) limb temps (calls are
            # sequential, so the working set does not stack per slot)
            extra = 129 + 256 + 35
        if K == 0:
            # two-phase phase 1 holds ~8 extra S-wide f32/i32 planes
            # live at once (hash, mantissa, score, error envelope,
            # top-2 masks) on top of the shared choose temps
            temps += 8
        per_lane = max(per_lane,
                       4 * (temps * S + 2 * R + P + extra))
    vmem_lanes = VMEM_BUDGET // max(per_lane, 1)
    lanes = min(LANES, vmem_lanes)
    if lanes < MIN_LANES:
        return 0, 0
    return 1 << (lanes.bit_length() - 1), vmem_lanes


def kernel_geometry(plan, n_cand: int) -> tuple[int, int, int]:
    """(pg_lanes, fold, groups) for a candidate-batched kernel build.

    ``fold`` candidates ride the lane axis of one grid cell, so the
    folded intermediates are (S, fold*pg_lanes). The cost that
    batching actually reduces is the per-issue overhead of each
    fetch/choose pass — (2R, P) weight loads, op issues — which is
    paid ``groups * l_total`` times per cell of ``pg_lanes`` PGs, so
    the figure of merit is per-PG passes ``groups / pg_lanes``
    (streaming FLOPs are identical for every geometry). That quotient
    only improves over the candidate-major baseline
    (``n_cand / plan.lanes``) when the fold comes out of VMEM
    HEADROOM — the gap between the LANES-capped cell width and the
    model's raw ``vmem_lanes`` budget — NOT out of the PG width: a
    fold carved from plan.lanes alone can never beat fold == 1.
    So this brute-forces fold in [1, n_cand] (n_cand is tiny) for
    the minimal groups/pg_lanes, with

    - fold * pg_lanes <= vmem_lanes  (the scoped-VMEM model bounds
      the folded working set),
    - pg_lanes <= plan.lanes  (the LANES cap keeps its role as the
      per-cell PG bound) and pg_lanes a power of two >= MIN_LANES
      (one lane tile; per-candidate column slices stay 128-aligned
      and relayout-free),
    - groups = ceil(n_cand / fold) level sweeps when VMEM cannot
      carry every candidate at once.

    fold == 1 (always admissible) degenerates to the pre-round-15
    candidate-major geometry, so eligibility never shrinks and the
    chosen geometry is never worse per PG than the old kernel."""
    best = None                          # (groups, pg_lanes, fold)
    for fold in range(1, n_cand + 1):
        width = min(plan.vmem_lanes // fold, plan.lanes)
        if width < MIN_LANES:
            break                        # width shrinks as fold grows
        pg = 1 << (width.bit_length() - 1)
        groups = -(-n_cand // fold)
        # better: fewer per-PG passes (groups/pg, compared exactly in
        # cross-multiplied integers); tie -> wider cells
        if best is None or groups * best[1] < best[0] * pg or \
                (groups * best[1] == best[0] * pg and pg > best[1]):
            best = (groups, pg, fold)
    groups, pg, fold = best              # fold=1 is always admissible
    return pg, fold, groups


def _bucket_classes(weights, G):
    """(cls per slot, class weights, raw weights) for the class draw,
    ("cont", None, raw weights) for the per-slot continuous draw, or
    None when the bucket fits neither model (a weight too large for
    the two-15-bit-halves table split, or no positive weight at all —
    the scalar rule hands an all-zero bucket to slot 0, which neither
    draw can express).

    Class draw: <= MAX_CLASSES distinct positive weights, each within
    the ln-gap license G (the within-class argmax argument needs it).
    Continuous draw (round 6, two-phase since round 10): anything else
    with 0 < w < 2^30 and at most MAX_CONT_SLOTS slots (bounding the
    level table's fetch width) — each slot is its own class, so no
    license applies."""
    ws = [int(w) for w in weights]
    if not any(w > 0 for w in ws):
        return None
    cls: list[int] | None = []
    cws: list[int] = []
    for w in ws:
        if w <= 0:
            cls.append(-1)       # zero-weight slot: never wins
            continue
        if w > G or (w not in cws and len(cws) >= MAX_CLASSES):
            cls = None           # outside the class model
            break
        if w in cws:
            cls.append(cws.index(w))
        else:
            cws.append(w)
            cls.append(len(cws) - 1)
    if cls is not None:
        return cls, cws, ws
    if len(ws) <= MAX_CONT_SLOTS and \
            all(w < MAX_CONT_WEIGHT for w in ws):
        return "cont", None, ws
    return None


# ---------------------------------------------------------------------------
# Plan: map -> per-level stratified tables
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)   # eq=False: identity
class KernelPlan:                               # hash -> usable as a
    """Host-built per-descent-level tables + static rule facts.

    The plan is a static jit argument compared BY IDENTITY — the Mapper
    builds it once per map and reuses the object, so each map compiles
    once.

    levels[l] is a (2*R_l, P_l) f32 table, transposed for the
    (rows, P) @ (P, N) MXU fetch: logical rows [0,S) item ids, [S,2S)
    next-level row index (device id at the last level), row 2S the
    bucket size; multi-class levels (kmax[l] > 1) append [2S+1,3S+1)
    per-slot class ids and 2*K rows of class-weight halves
    (w & 0x7FFF, w >> 15); continuous levels (kmax[l] == 0) append
    [2S+1,3S+1) per-slot weight low halves and [3S+1,4S+1) high
    halves instead. Each logical value v is stored as TWO byte
    planes lo=(v+32768)&0xFF (rows [0,R)) and hi=(v+32768)>>8 (rows
    [R,2R)), both in [0,256) and hence EXACT in one bf16 MXU pass
    (DEFAULT precision; HIGHEST's 6 passes made this fetch the
    kernel's dominant cost — measured 6x on the canonical map's
    640-host level). build_plan declines maps with |value| >= 32768.
    """

    levels: tuple          # tuple of np.ndarray (f32)
    sizes: tuple           # (S_l, P_l) pairs, static
    rows: tuple            # logical row count R_l per level (2S+1 for
                           # uniform levels; 3S+1+2K for class levels)
    kmax: tuple            # weight classes per level (1 = uniform
                           # draw, 0 = per-slot continuous draw)
    l_main: int            # levels from root to the target type
    l_leaf: int            # levels from target type to devices
    numrep_arg: int        # rule's arg1 (0 = fill result_max)
    recurse: bool          # chooseleaf?
    vary_r: int
    tries: int
    target_type: int
    rw_ids: np.ndarray     # (K,) int32 non-full device ids (maybe empty)
    rw_w: np.ndarray       # (K,) int32 their 16.16 reweights
    zg2dT: np.ndarray      # (256, 256) f32 {0,1}, [lo, hi] ln-equality
    rhlh: np.ndarray | None  # (14, 129) f32 RH/LH byte planes, or None
    ll: np.ndarray | None    # (6, 256) f32 LL byte planes, or None
    lanes: int             # max PG cell width (LANES cap ∧ VMEM
                           # model); kernel_geometry picks the actual
                           # per-numrep cell width and candidate fold
    vmem_lanes: int        # RAW VMEM lane budget (uncapped) — the
                           # headroom the candidate fold spends


def build_plan(m: CrushMap, packed, ruleno: int,
               device_weights: np.ndarray | None = None,
               choose_args_key=None
               ) -> KernelPlan | tuple[KernelPlan, ...] | None:
    """Stratify the map for one rule: the ``KernelPlan`` of a rule of
    one take/emit block; for a rule of several (the docs' SSD-primary
    ``mixed_replicated_rule``), a tuple of plans, one a block in the
    rule's order, which the caller runs one after another and merges as
    firstn EMIT does, keeping a lane's first ``result_max`` items in
    block then slot order (no collision scan crosses blocks: each
    chooses into its own working vector, ref: mapper.c crush_do_rule).
    None if any block is ineligible: the whole rule keeps the XLA
    path."""
    t = m.tunables
    if t.chooseleaf_stable != 1 or t.choose_local_tries or \
            t.choose_local_fallback_tries:
        return None
    # choose_args (round 5): a balancer weight-set substitutes the draw
    # weights per bucket. With a SINGLE weight set the substitution is
    # position-independent, so the shared-candidate-table trick still
    # holds and the class machinery absorbs it; per-position sets or
    # hash-id overrides break those assumptions -> XLA path.
    ca_map = None
    if choose_args_key is not None and choose_args_key in m.choose_args:
        ca_map = m.choose_args[choose_args_key]
        for ca in ca_map.values():
            if getattr(ca, "ids", None):
                return None
            if ca.weight_set and len(ca.weight_set) != 1:
                return None
    rule = m.rules.get(ruleno) if isinstance(m.rules, dict) \
        else (m.rules[ruleno] if ruleno < len(m.rules) else None)
    if rule is None:
        return None
    steps = [s for s in rule.steps if s.op != OP_NOOP]
    # the rule's take/emit blocks: the steps cut after each EMIT, every
    # block TAKE / CHOOSE[LEAF]_FIRSTN / EMIT
    blocks = [steps[i:i + 3] for i in range(0, len(steps), 3)]
    if not blocks or any(
            len(b) != 3 or b[0].op != OP_TAKE or b[2].op != OP_EMIT
            or b[1].op not in (OP_CHOOSELEAF_FIRSTN, OP_CHOOSE_FIRSTN)
            for b in blocks):
        return None
    from ceph_tpu.crush.ln_table import ln_gap_info
    G, zg = ln_gap_info()
    plans = []
    for take, choose, _emit in blocks:
        plan = _block_plan(m, take.arg1, choose, device_weights, ca_map,
                           G, zg)
        if plan is None:
            return None
        plans.append(plan)
    return plans[0] if len(plans) == 1 else tuple(plans)


def _block_plan(m: CrushMap, root: int, choose, device_weights, ca_map,
                G, zg) -> KernelPlan | None:
    """The plan of one take/emit block: ``take root`` then ``choose``,
    or None if the kernel cannot run it."""
    t = m.tunables
    recurse = choose.op == OP_CHOOSELEAF_FIRSTN
    target_type = choose.arg2
    if recurse and target_type == 0:
        return None
    if root >= 0 or root not in m.buckets:
        return None
    # BFS strata: level l = all buckets at depth l from the root; the
    # kernel requires every level to be "pure" (all buckets, or all
    # devices at the end) and the target type to sit at one depth.
    bucket_cls: dict[int, tuple] = {}       # bid -> (cls per slot, cws)
    strata: list[list[int]] = [[root]]
    l_main = None
    while True:
        cur = strata[-1]
        for bid in cur:
            b = m.buckets[bid]
            if b.alg != ALG_STRAW2 or b.size == 0:
                return None
            if bid not in bucket_cls:
                ws = b.weights
                if ca_map is not None and bid in ca_map:
                    ca = ca_map[bid]
                    if ca.weight_set:
                        if len(ca.weight_set[0]) != b.size:
                            return None
                        ws = ca.weight_set[0]
                info = _bucket_classes(ws, G)
                if info is None:
                    return None
                bucket_cls[bid] = info
        types = {m.buckets[bid].type for bid in cur}
        if len(strata) - 1 > 0 or True:
            if types == {target_type}:
                if l_main is not None:
                    return None
                l_main = len(strata) - 1
            elif target_type in types:
                return None                     # mixed target level
        children: list[int] = []
        seen = set()
        kinds = set()
        for bid in cur:
            for it in m.buckets[bid].items:
                kinds.add(it >= 0)
                if it < 0 and it not in seen:
                    if it not in m.buckets:
                        return None
                    seen.add(it)
                    children.append(it)
        if len(kinds) > 1:
            return None                         # devices mixed w/ buckets
        if kinds == {True}:                     # next level is devices
            break
        if len(strata) > 12:
            return None
        strata.append(children)
    if l_main is None:
        # CHOOSE_FIRSTN type 0 straight to devices: target level is the
        # device level
        if not recurse and target_type == 0:
            l_main = len(strata)
        else:
            return None
    l_total = len(strata)                       # levels of bucket choice
    l_leaf = l_total - l_main
    if recurse and l_leaf < 1:
        return None
    if not recurse and l_leaf != 0:
        return None
    # reweight eligibility
    max_dev = -1
    for bid in strata[-1]:
        for it in m.buckets[bid].items:
            max_dev = max(max_dev, it)
    if device_weights is None:
        rw_ids = np.zeros(0, dtype=np.int32)
        rw_w = np.zeros(0, dtype=np.int32)
    else:
        dw = np.asarray(device_weights)
        if max_dev >= dw.shape[0]:
            return None                         # out-of-range device ids
        nonfull = np.nonzero(dw[:max_dev + 1] != WEIGHT_ONE)[0]
        if nonfull.shape[0] > MAX_REWEIGHT:
            return None
        rw_ids = nonfull.astype(np.int32)
        rw_w = dw[nonfull].astype(np.int32)
    # per-level tables
    row_index = [{bid: i for i, bid in enumerate(lvl)} for lvl in strata]
    levels = []
    sizes = []
    rows = []
    kmax = []
    for li, lvl in enumerate(strata):
        S = max(m.buckets[bid].size for bid in lvl)
        P = len(lvl)
        # A level holding ANY continuous bucket takes the per-slot
        # layout for all its buckets (per-slot weights express class
        # buckets too); kmax = 0 marks it. Single-class levels keep
        # the lean uniform layout; multi-class levels append per-slot
        # class ids and per-class weight halves (w <= G < 2^29 splits
        # into two sub-32768 values, so the same biased byte-plane
        # fetch stays exact).
        cont_l = any(bucket_cls[bid][0] == "cont" for bid in lvl)
        if not cont_l and \
                max(len(bucket_cls[bid][1]) for bid in lvl) == 1 and \
                any(-1 in bucket_cls[bid][0] for bid in lvl):
            # one weight class and a zero-weight slot (an OSD at CRUSH
            # weight 0, a drained entry of a weight-set): the uniform
            # layout has no row to say a slot is dead and would let it
            # win. The per-slot layout draws a dead slot with w = 0,
            # which never wins (_choose_level_cont's ``live``).
            if any(w >= MAX_CONT_WEIGHT
                   for bid in lvl for w in bucket_cls[bid][2]):
                return None
            cont_l = True
        if cont_l and S > MAX_CONT_SLOTS:
            # the continuous layout's table rows (4S+1) and phase-1
            # temps scale with the LEVEL's padded width S, not each
            # continuous bucket's own size — a wide uniform sibling
            # sharing the stratum widens the whole level, so the cap
            # applies to S
            return None
        K = 0 if cont_l else \
            max(len(bucket_cls[bid][1]) for bid in lvl)
        if cont_l:
            R = 4 * S + 1        # + per-slot weight halves
        elif K == 1:
            R = 2 * S + 1
        else:
            R = 3 * S + 1 + 2 * K
        tbl = np.zeros((R, P), dtype=np.int64)
        for p, bid in enumerate(lvl):
            b = m.buckets[bid]
            tbl[:b.size, p] = b.items
            if li + 1 < l_total:
                tbl[S:S + b.size, p] = [row_index[li + 1][it]
                                        for it in b.items]
            else:
                tbl[S:S + b.size, p] = b.items   # device ids
            tbl[2 * S, p] = b.size
            if cont_l:
                ws = bucket_cls[bid][2]
                for s, w in enumerate(ws):
                    w = max(int(w), 0)   # dead slots draw with w=0
                    tbl[2 * S + 1 + s, p] = w & 0x7FFF
                    tbl[3 * S + 1 + s, p] = w >> 15
            elif K > 1:
                cls, cws, _ = bucket_cls[bid]
                # zero-weight (-1) and padding slots get class K: they
                # match no class and can never win
                tbl[2 * S + 1:2 * S + 1 + S, p] = K
                tbl[2 * S + 1:2 * S + 1 + b.size, p] = [
                    c if c >= 0 else K for c in cls]
                for c, w in enumerate(cws):
                    tbl[3 * S + 1 + c, p] = w & 0x7FFF
                    tbl[3 * S + 1 + K + c, p] = w >> 15
        if tbl.min() < -32768 or tbl.max() >= 32768:
            return None      # byte-plane split covers [-32768, 32768)
        biased = tbl + 32768                     # [0, 65536)
        # (measured: 8-aligning the sections/lanes for relayout-free
        # slices was 8% SLOWER and crashed Mosaic on 1-wide blocks —
        # the simple layout wins; see BASELINE.md kernel-cost table)
        split = np.concatenate([biased & 0xFF, biased >> 8],
                               axis=0).astype(np.float32)
        levels.append(split)
        sizes.append((S, P))
        rows.append(R)
        kmax.append(K)
    # f32, not int8: Mosaic cannot lower int32->int8 casts (the
    # bool one-hot would recurse through _convert_helper); the table
    # holds only {0,1} so f32 is exact. Only hi bytes >= 128 ever have
    # an equality pair (min zg index is 33023 = 0x80FF: iexpon-15
    # territory, where crush_ln's gaps shrink below 1), so the hi
    # one-hot needs 128 rows, halving the per-choose matmul.
    zg2 = zg.reshape(256, 256)                      # [hi, lo]
    assert not zg2[:128].any(), "zg pairs must all have hi >= 128"
    zg2dT = np.ascontiguousarray(
        zg2[128:].T).astype(np.float32)             # (256 lo, 128 hi)
    rhlh = ll = None
    if any(k != 1 for k in kmax):     # class (>1) or continuous (0)
        rhlh, ll = _ln_plane_tables()
    lanes, vmem_lanes = _plan_lanes(sizes, rows, kmax)
    if not lanes:
        return None          # flat/huge-bucket map: the per-cell working
                             # set cannot fit scoped VMEM at any useful
                             # width — the XLA path is the right tool
    return KernelPlan(
        levels=tuple(levels), sizes=tuple(sizes),
        rows=tuple(rows), kmax=tuple(kmax),
        l_main=l_main, l_leaf=l_leaf,
        numrep_arg=choose.arg1, recurse=recurse,
        vary_r=t.chooseleaf_vary_r, tries=t.choose_total_tries,
        target_type=target_type, rw_ids=rw_ids, rw_w=rw_w,
        zg2dT=zg2dT, rhlh=rhlh, ll=ll, lanes=lanes,
        vmem_lanes=vmem_lanes)


@functools.lru_cache(maxsize=1)
def _ln_plane_tables():
    """crush_ln's RH/LH (129-entry) and LL (256-entry) tables as f32
    byte planes for the in-kernel one-hot MXU fetch (same exactness
    argument as the level-table fetch: every plane value < 256, one-hot
    weights are {0,1}, so one DEFAULT-precision bf16 pass with f32
    accumulation is exact). RH <= 2^48 and LH can be exactly 2^48, so
    both take 7 planes; LL < 2^42 takes 6."""
    from ceph_tpu.crush.ln_table import ll_table, rh_lh_tables
    rh, lh = rh_lh_tables()
    ll = ll_table()
    rhlh = np.empty((14, 129), dtype=np.float32)
    for i in range(7):
        rhlh[i] = ((rh >> np.uint64(8 * i)) & np.uint64(0xFF))
        rhlh[7 + i] = ((lh >> np.uint64(8 * i)) & np.uint64(0xFF))
    llp = np.empty((6, 256), dtype=np.float32)
    for i in range(6):
        llp[i] = ((ll >> np.uint64(8 * i)) & np.uint64(0xFF))
    return rhlh, llp


# ---------------------------------------------------------------------------
# In-kernel primitives
# ---------------------------------------------------------------------------

def _srl(v, n):
    return jax.lax.shift_right_logical(v, jnp.int32(n))


def _mix(a, b, c):
    """crush_hashmix in int32 (bit-identical to C uint32: add/sub/xor/
    shl wrap two's-complement; right shifts are explicit logical)."""
    a = (a - b) - c
    a = a ^ _srl(c, 13)
    b = (b - c) - a
    b = b ^ (a << 8)
    c = (c - a) - b
    c = c ^ _srl(b, 13)
    a = (a - b) - c
    a = a ^ _srl(c, 12)
    b = (b - c) - a
    b = b ^ (a << 16)
    c = (c - a) - b
    c = c ^ _srl(b, 5)
    a = (a - b) - c
    a = a ^ _srl(c, 3)
    b = (b - c) - a
    b = b ^ (a << 10)
    c = (c - a) - b
    c = c ^ _srl(b, 15)
    return a, b, c


def _hash3(a, b, c):
    """crush_hash32_rjenkins1_3 (ref: src/crush/hash.c)."""
    h = jnp.int32(CRUSH_HASH_SEED) ^ a ^ b ^ c
    x = jnp.int32(231232)
    y = jnp.int32(1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def _hash2(a, b):
    h = jnp.int32(CRUSH_HASH_SEED) ^ a ^ b
    x = jnp.int32(231232)
    y = jnp.int32(1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def _zg_flag(zg_ref, umax):
    """(1, N) int32 in {0,1}: crush_ln(umax-1) == crush_ln(umax)?

    The tie between draw umax and umax-1 exists iff they are an
    ln-equality pair (ln_gap_info); factored (256, 256) int8 table,
    fetched with an int8 MXU matmul + sublane select."""
    if "nozg" in _ABLATE:                            # pragma: no cover
        return jnp.zeros_like(umax)
    vm1 = jnp.maximum(umax - 1, 0)
    hi = (_srl(vm1, 8) & 0xFF) - 128     # zg rows cover hi in [128,256)
    lo = vm1 & 0xFF
    iota = jax.lax.broadcasted_iota(jnp.int32, (256, umax.shape[1]), 0)
    hiota = jax.lax.broadcasted_iota(jnp.int32, (128, umax.shape[1]), 0)
    oh_hi = (hiota == hi).astype(jnp.float32)        # (128, N); hi < 0
    # (no pair possible) matches no row -> flag 0 with no extra select.
    # DEFAULT precision: one bf16 MXU pass is EXACT here — both
    # operands are {0,1} (bf16-representable) and accumulation is f32;
    # this is the kernel's hot matmul (one per choose), so the 6-pass
    # HIGHEST the id-fetch needs would cost 6x for nothing.
    rowv = jax.lax.dot_general(
        zg_ref[...], oh_hi, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)                              # (256lo, N) {0,1}
    sel = (iota == lo).astype(jnp.int32)
    # dtype=int32: under enable_x64 jnp.sum would promote to an int64
    # accumulator (numpy rules) — Mosaic has no int64, and the int64->
    # int32 cast recurses forever in its _convert_helper; an explicit
    # accumulator dtype never creates the int64 in the first place
    flag = jnp.sum(rowv * sel, axis=0, keepdims=True, dtype=jnp.int32)
    # scalar literals in jnp.where must be explicit int32: under
    # enable_x64 a Python int traces as an i64[] constant whose
    # i64->i32 convert Mosaic cannot lower (recurses in
    # _convert_helper)
    return jnp.where(umax > 0, flag, jnp.int32(0))


def _onehot_fetch(tab_ref, idx, entries):
    """(planes, N) f32 rows of ``tab_ref`` selected per lane by ``idx``
    ((1, N) int32 in [0, entries)) via a one-hot bf16 MXU matmul —
    exact: plane values < 256, weights {0,1}, f32 accumulation."""
    n = idx.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (entries, n), 0)
    oh = (iota == idx).astype(jnp.float32)
    return jax.lax.dot_general(
        tab_ref[...], oh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _crush_ln_neg(rhlh_ref, ll_ref, v):
    """neg = 2^48 - crush_ln(v) for v (1, N) int32 in [0, 0xFFFF],
    bit-exact vs ln_table.crush_ln, as (hi, lo) 24-bit int32 limbs.

    Mirrors the fixed-point path (ref: src/crush/mapper.c crush_ln) in
    lane-parallel int32: normalize x = v+1 into [0x8000, 0x10000]
    (iexpon), fetch RH/LH by the 129-entry one-hot, walk the 17x49-bit
    product x_norm * RH byte-by-byte to get the residual index2 (only
    byte 6 of the product is consumed, so a running-carry ladder of
    7 sub-2^25 partials suffices), fetch LL, and assemble
    (iexpon << 44) + ((LH + LL) >> 4) in two 24-bit limbs."""
    x = v + jnp.int32(1)                             # [1, 0x10000]
    nb = jnp.zeros_like(x)
    vv = x
    for b in (16, 8, 4, 2, 1):                       # bit_length
        big = vv >= jnp.int32(1 << b)
        nb = jnp.where(big, nb + jnp.int32(b), nb)
        vv = jnp.where(big, _srl(vv, b), vv)
    shift = jnp.maximum(jnp.int32(15) - nb, jnp.int32(0))
    xn = x << shift                                  # [0x8000, 0x10000]
    iexpon = jnp.int32(15) - shift
    j = _srl(xn, 8) - jnp.int32(128)                 # [0, 128]
    pl = _onehot_fetch(rhlh_ref, j, 129).astype(jnp.int32)  # (14, N)
    # index2 = ((xn * RH) >> 48) & 0xFF via the byte ladder: partials
    # xn * rh_byte <= 2^16 * 255 < 2^24, acc < 2^25 — int32 throughout
    acc = xn * pl[0:1, :]
    for i in range(1, 7):
        acc = _srl(acc, 8) + xn * pl[i:i + 1, :]
    index2 = acc & jnp.int32(0xFF)
    lp = _onehot_fetch(ll_ref, index2, 256).astype(jnp.int32)  # (6, N)
    # LH + LL in 24-bit limbs (LH byte 6 is <= 1: the 2^48 endpoint)
    lh_lo = pl[7:8] + (pl[8:9] << 8) + (pl[9:10] << 16)
    lh_hi = pl[10:11] + (pl[11:12] << 8) + (pl[12:13] << 16) \
        + (pl[13:14] << 24)
    ll_lo = lp[0:1] + (lp[1:2] << 8) + (lp[2:3] << 16)
    ll_hi = lp[3:4] + (lp[4:5] << 8) + (lp[5:6] << 16)
    slo = lh_lo + ll_lo                              # < 2^25
    shi = lh_hi + ll_hi + _srl(slo, 24)
    slo = slo & jnp.int32(0xFFFFFF)
    # ln = (iexpon << 44) + ((LH + LL) >> 4), limbs (hi 24..47, lo 0..23)
    ln_lo = _srl(slo, 4) | ((shi & jnp.int32(0xF)) << 20)
    ln_hi = _srl(shi, 4) + (iexpon << 20)
    # neg = 2^48 - ln
    borrow = (ln_lo > 0).astype(jnp.int32)
    neg_lo = (jnp.int32(1 << 24) - ln_lo) & jnp.int32(0xFFFFFF)
    neg_hi = jnp.int32(1 << 24) - ln_hi - borrow
    return neg_hi, neg_lo


def _choose_level_cls(zg_ref, rhlh_ref, ll_ref, x_row, ids, rows_next,
                      size, cls, wlo, whi, K, r):
    """One straw2 choose over (S, N) slots with K weight classes.

    The scalar spec's winner is the FIRST slot attaining the maximal
    draw, draw = trunc((crush_ln(u) - 2^48) / w) (ref: mapper.c
    bucket_straw2_choose + div64_s64) — equivalently the minimal
    truncated quotient q = neg // w. Decomposed by weight class:
    within a class (one w <= G) the minimal q is attained exactly by
    the ln-equality class of the maximal hash — the round-3 uniform
    argument — so only ONE exact crush_ln per class is needed, and the
    cross-class winner is decided by comparing d_c = neg_c / w_c in
    f32. Lanes whose top two d_c land within MARGIN (covering all f32
    rounding and integer floor ties) return amb=1 and are recomputed
    bit-exactly by the caller's XLA fallback; everywhere else the f32
    order provably equals the exact truncated-quotient order."""
    S, N = ids.shape
    xb = jnp.broadcast_to(x_row, (S, N))
    rb = jnp.broadcast_to(jnp.asarray(r, jnp.int32), (S, N))
    if "nohash" in _ABLATE:                          # pragma: no cover
        u = (xb ^ ids ^ rb) & 0xFFFF
    else:
        u = _hash3(xb, ids, rb) & 0xFFFF             # (S, N)
    slot = jax.lax.broadcasted_iota(jnp.int32, (S, N), 0)
    valid = slot < size
    big = jnp.float32(3.0e38)
    best_d = jnp.full((1, N), big, dtype=jnp.float32)
    second_d = jnp.full((1, N), big, dtype=jnp.float32)
    best_c = jnp.zeros((1, N), dtype=jnp.int32)
    best_u = jnp.zeros((1, N), dtype=jnp.int32)
    for c in range(K):
        mask = valid & (cls == c)
        um = jnp.where(mask, u, jnp.int32(-1))
        umax = jnp.max(um, axis=0, keepdims=True)    # (1, N)
        nh, nl = _crush_ln_neg(rhlh_ref, ll_ref,
                               jnp.maximum(umax, 0))
        w_f = whi[c:c + 1, :].astype(jnp.float32) * jnp.float32(32768.0) \
            + wlo[c:c + 1, :].astype(jnp.float32)
        neg_f = nh.astype(jnp.float32) * jnp.float32(16777216.0) \
            + nl.astype(jnp.float32)
        d = neg_f / jnp.maximum(w_f, jnp.float32(1.0))
        d = jnp.where((umax >= 0) & (w_f > 0), d, big)
        new_min = d < best_d
        second_d = jnp.where(new_min, best_d, jnp.minimum(second_d, d))
        best_c = jnp.where(new_min, jnp.int32(c), best_c)
        best_u = jnp.where(new_min, umax, best_u)
        best_d = jnp.minimum(best_d, d)
    margin = jnp.float32(MARGIN_ABS) + best_d * jnp.float32(MARGIN_REL)
    amb = (second_d - best_d) <= margin              # (1, N) bool
    thresh = best_u - _zg_flag(zg_ref, best_u)
    member = valid & (cls == best_c) & (u >= thresh)
    kk = jnp.where(member, slot, jnp.int32(S))
    kmin = jnp.min(kk, axis=0, keepdims=True)
    sel = (slot == kmin).astype(jnp.int32)
    win_id = jnp.sum(sel * ids, axis=0, keepdims=True,
                     dtype=jnp.int32)
    win_next = jnp.sum(sel * rows_next, axis=0, keepdims=True,
                       dtype=jnp.int32)
    return win_id, win_next, amb


def _approx_z(u):
    """(S, N) int32 hash -> (S, N) f32 ~ (2^48 - crush_ln(u)) / 2^44.

    Phase-1 scorer: exact exponent/mantissa split of y = u+1 (the
    bit-length ladder mirrors _crush_ln_neg's normalize; t = y*2^-e - 1
    is EXACT in f32 because y*2^(16-e) is an integer < 2^17), then a
    degree-7 polynomial for log2(1+t) in Horner form. Pure elementwise
    f32 over the whole slot plane — no table fetch, no per-slot unroll.
    |result - exact| <= ERR_Z over the entire 16-bit domain (verified
    exhaustively; crush_ln's own index2 staircase dominates)."""
    y = u + jnp.int32(1)                             # [1, 0x10000]
    nb = jnp.zeros_like(y)
    v = y
    for b in (16, 8, 4, 2, 1):                       # floor(log2(y))
        big = v >= jnp.int32(1 << b)
        nb = jnp.where(big, nb + jnp.int32(b), nb)
        v = jnp.where(big, _srl(v, b), v)
    pow2 = jnp.int32(1) << (jnp.int32(16) - nb)
    t = (y.astype(jnp.float32) * pow2.astype(jnp.float32)
         ) * jnp.float32(2.0 ** -16) - jnp.float32(1.0)   # [0, 1)
    acc = jnp.full(t.shape, _LOG2_POLY[-1], dtype=jnp.float32)
    for c in _LOG2_POLY[-2::-1]:
        acc = acc * t + jnp.float32(c)
    return (jnp.float32(16.0) - nb.astype(jnp.float32)) - acc


def _choose_level_cont(rhlh_ref, ll_ref, x_row, ids, rows_next, size,
                       wlo, whi, r):
    """Two-phase straw2 choose over (S, N) slots with ARBITRARY
    per-slot weights — the continuous-choose_args / many-distinct-
    disks case that used to gate the whole map off the kernel.

    Every slot is its own weight class (the degenerate class
    decomposition — no within-class tie to break, so no ln-gap
    license applies). Round 6 ran the exact crush_ln ladder once per
    slot, sequentially; this version (round 10):

    - phase 1 scores ALL slots in one fused elementwise pass with the
      _approx_z f32 approximation (proven |err| <= ERR_Z over the full
      hash domain) and selects the top-2 candidates plus a lower
      envelope over every remaining slot;
    - phase 2 runs the exact fixed-point ladder (_crush_ln_neg —
      bit-exact vs ln_table.crush_ln) on JUST those two candidates and
      compares their exact draws in f32.

    The scalar winner is the FIRST slot attaining the minimal
    truncated quotient (mapper.c bucket_straw2_choose keeps the
    incumbent on draw ties); strict exact-f32 comparison reproduces it
    whenever the gap clears MARGIN_ABS + best*MARGIN_REL (the round-6
    envelope covering f32 rounding and integer floor ties). amb=1 —
    recompute bit-exactly on the caller's XLA fallback — when (a) the
    top-2 exact draws land inside that margin, or (b) any third slot's
    phase-1 score minus its error bound reaches the winner's exact
    draw plus the margin: if the exact winner were outside the phase-1
    top-2, its own lower bound would trip (b), so no unflagged lane
    can misrank."""
    S, N = ids.shape
    xb = jnp.broadcast_to(x_row, (S, N))
    rb = jnp.broadcast_to(jnp.asarray(r, jnp.int32), (S, N))
    if "nohash" in _ABLATE:                          # pragma: no cover
        u = (xb ^ ids ^ rb) & 0xFFFF
    else:
        u = _hash3(xb, ids, rb) & 0xFFFF             # (S, N)
    big = jnp.float32(3.0e38)
    slot = jax.lax.broadcasted_iota(jnp.int32, (S, N), 0)
    w_f = whi.astype(jnp.float32) * jnp.float32(32768.0) \
        + wlo.astype(jnp.float32)                    # (S, N)
    live = (slot < size) & (w_f > 0)   # dead: past size, or w <= 0
    # phase 1: fused approximate scoring of every slot at once
    d_a = (_approx_z(u) * jnp.float32(2.0 ** 44)) \
        / jnp.maximum(w_f, jnp.float32(1.0))
    d_a = jnp.where(live, d_a, big)
    err = jnp.float32(ERR_Z * 2.0 ** 44) \
        / jnp.maximum(w_f, jnp.float32(1.0)) \
        + d_a * jnp.float32(REL_SLOP)
    b1 = jnp.min(d_a, axis=0, keepdims=True)         # (1, N)
    k1 = jnp.min(jnp.where(d_a == b1, slot, jnp.int32(S)),
                 axis=0, keepdims=True)
    m1 = slot == k1
    d_a2 = jnp.where(m1, big, d_a)
    b2 = jnp.min(d_a2, axis=0, keepdims=True)
    k2 = jnp.min(jnp.where(d_a2 == b2, slot, jnp.int32(S)),
                 axis=0, keepdims=True)
    # no second LIVE candidate (single-live-slot bucket): b2 stays at
    # `big` and k2 would collapse onto slot 0 — possibly k1 itself,
    # making d2==d1 flag every lane. Mask m2 off instead: the lone
    # candidate is trivially unambiguous.
    m2 = (slot == k2) & (b2 < big)
    # lower envelope over every slot OUTSIDE the top-2: if any could
    # still beat the winner once its proven error is granted, flag
    low3 = jnp.min(jnp.where(live & ~m1 & ~m2, d_a - err, big),
                   axis=0, keepdims=True)

    # phase 2: the exact ladder on just the two candidates
    def _cand(m):
        mi = m.astype(jnp.int32)
        uu = jnp.sum(mi * u, axis=0, keepdims=True, dtype=jnp.int32)
        ww = jnp.sum(m.astype(jnp.float32) * w_f, axis=0,
                     keepdims=True)
        ii = jnp.sum(mi * ids, axis=0, keepdims=True, dtype=jnp.int32)
        nn = jnp.sum(mi * rows_next, axis=0, keepdims=True,
                     dtype=jnp.int32)
        alive = jnp.sum(mi * live.astype(jnp.int32), axis=0,
                        keepdims=True, dtype=jnp.int32) > 0
        nh, nl = _crush_ln_neg(rhlh_ref, ll_ref, uu)
        neg_f = nh.astype(jnp.float32) * jnp.float32(16777216.0) \
            + nl.astype(jnp.float32)
        d = neg_f / jnp.maximum(ww, jnp.float32(1.0))
        return ii, nn, jnp.where(alive, d, big)

    i1, n1, d1 = _cand(m1)
    i2, n2, d2 = _cand(m2)
    best = jnp.minimum(d1, d2)
    take2 = d2 < d1
    win_id = jnp.where(take2, i2, i1)
    win_next = jnp.where(take2, n2, n1)
    margin = jnp.float32(MARGIN_ABS) + best * jnp.float32(MARGIN_REL)
    amb = (jnp.maximum(d1, d2) - best) <= margin
    amb = amb | (low3 <= best + margin)
    return win_id, win_next, amb


def _choose_level(zg_ref, x_row, ids, rows_next, size, r):
    """One straw2 uniform-weight choose over (S, N) candidate slots.

    ids/rows_next: (S, N) int32; size: (1, N) int32 live-slot count;
    r: (1, N) or scalar int32. Returns (win_id, win_next) each (1, N).
    Winner = first slot among the ln-equality class of the max 16-bit
    hash (ref: mapper.c bucket_straw2_choose keeps the incumbent on
    draw ties -> first index wins; ln_table.ln_gap_info licenses the
    hash-only formulation for uniform weights)."""
    S, N = ids.shape
    xb = jnp.broadcast_to(x_row, (S, N))
    rb = jnp.broadcast_to(jnp.asarray(r, jnp.int32), (S, N)) \
        if not hasattr(r, "shape") or r.shape != (S, N) \
        else r
    if "nohash" in _ABLATE:                          # pragma: no cover
        u = (xb ^ ids ^ rb) & 0xFFFF
    else:
        u = _hash3(xb, ids, rb) & 0xFFFF             # (S, N)
    slot = jax.lax.broadcasted_iota(jnp.int32, (S, N), 0)
    valid = slot < size                              # (S, N)
    um = jnp.where(valid, u, jnp.int32(-1))   # int32: see _zg_flag
    umax = jnp.max(um, axis=0, keepdims=True)        # (1, N)
    thresh = umax - _zg_flag(zg_ref, umax)
    member = valid & (um >= thresh)
    kk = jnp.where(member, slot, jnp.int32(S))
    kmin = jnp.min(kk, axis=0, keepdims=True)        # first member slot
    sel = (slot == kmin).astype(jnp.int32)
    # dtype=int32: see _zg_flag — the x64 sum promotion must neither
    # leak int64 into the reweight branch's _hash2 nor emit an
    # int64->int32 cast (unlowerable on Mosaic)
    win_id = jnp.sum(sel * ids, axis=0, keepdims=True,
                     dtype=jnp.int32)
    win_next = jnp.sum(sel * rows_next, axis=0, keepdims=True,
                       dtype=jnp.int32)
    return win_id, win_next


def _fetch_level(tbl_ref, S, P, R, row, n):
    """Row tables for per-lane rows via a one-hot bf16 MXU matmul.

    The table stores each value as two byte planes (build_plan), both
    in [0,256) and so EXACT under DEFAULT precision's single bf16 pass
    — this fetch was the kernel's dominant cost at HIGHEST (6 passes;
    doubling the rows costs nothing here because row counts sit far
    below the MXU's 128-row tile).

    Returns the debiased logical rows, (R, N) int32 — [0,S) item ids,
    [S,2S) next rows, [2S] size, plus the class rows when present."""
    if P == 1 or "nofetch" in _ABLATE:
        col = tbl_ref[...][:, 0:1]                   # (2R, 1)
        planes = jnp.broadcast_to(col, (2 * R, n))
    else:
        iota = jax.lax.broadcasted_iota(jnp.int32, (P, n), 0)
        onehot = (iota == row).astype(jnp.float32)   # (P, N)
        planes = jax.lax.dot_general(
            tbl_ref[...], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (2R, N)
    # recombine: hi*256 + lo <= 65535 is exact in f32; debias after
    return (planes[R:2 * R, :] * jnp.float32(256.0) +
            planes[0:R, :]).astype(jnp.int32) - jnp.int32(32768)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------

def _make_kernel(plan: KernelPlan, numrep: int, n_cand: int,
                 skip_rw: bool, fold: int):
    l_total = plan.l_main + plan.l_leaf
    S_list = [s for s, _ in plan.sizes]
    P_list = [p for _, p in plan.sizes]
    R_list = list(plan.rows)
    K_list = list(plan.kmax)
    any_cls = any(k != 1 for k in K_list)    # class or continuous
    K = plan.rw_ids.shape[0]

    def kernel(*refs):
        xs_ref = refs[0]
        tbl_refs = refs[1:1 + l_total]
        zg_ref = refs[1 + l_total]
        nref = 2 + l_total
        rhlh_ref = ll_ref = None
        if any_cls:
            rhlh_ref = refs[nref]
            ll_ref = refs[nref + 1]
            nref += 2
        out_ref = refs[nref]
        bad_ref = refs[nref + 1]
        x = xs_ref[...]                              # (1, N) int32
        n = x.shape[1]
        amb_any = jnp.zeros((1, n), dtype=jnp.bool_)
        items_c = []
        leaves_c = []
        ok_c = []
        # Level-major candidate-batched descent (round 15): `fold`
        # candidates ride the lane axis per group — each level runs
        # ONE fetch and ONE choose for all of them, with a per-column
        # r vector (the choose functions broadcast (1, N)-shaped r
        # over the slot axis, so the per-column math is the old math).
        for g0 in range(0, n_cand, fold):
            cands = list(range(g0, min(g0 + fold, n_cand)))
            nf = len(cands)
            nw = nf * n
            xw = x if nf == 1 else jnp.concatenate([x] * nf, axis=1)

            def _rvec(vals):
                cols = [jnp.full((1, n), int(v), dtype=jnp.int32)
                        for v in vals]
                return cols[0] if nf == 1 else \
                    jnp.concatenate(cols, axis=1)

            # main descent at r; leaf descent at sub_r (descend_once)
            r_main = _rvec(cands)
            r_leaf = _rvec([(c >> (plan.vary_r - 1))
                            if plan.vary_r else 0 for c in cands])
            row = jnp.zeros((1, nw), dtype=jnp.int32)
            amb_w = jnp.zeros((1, nw), dtype=jnp.bool_)
            item = None
            for li in range(l_total):
                S = S_list[li]
                # level 0 is the hoisted shared-root fetch: its
                # stratum is the single TAKE root (P == 1), so
                # _fetch_level broadcasts one column — no matmul, one
                # load serving every candidate in the group
                full = _fetch_level(
                    tbl_refs[li], S, P_list[li], R_list[li], row, nw)
                ids = full[0:S, :]
                nxt = full[S:2 * S, :]
                size = full[2 * S:2 * S + 1, :]
                rr = r_main if li < plan.l_main else r_leaf
                if K_list[li] == 1:
                    win_id, win_next = _choose_level(
                        zg_ref, xw, ids, nxt, size, rr)
                elif K_list[li] == 0:        # per-slot continuous draw
                    win_id, win_next, amb = _choose_level_cont(
                        rhlh_ref, ll_ref, xw, ids, nxt, size,
                        full[2 * S + 1:3 * S + 1, :],
                        full[3 * S + 1:4 * S + 1, :],
                        rr)
                    amb_w = amb_w | amb
                else:
                    kk = K_list[li]
                    win_id, win_next, amb = _choose_level_cls(
                        zg_ref, rhlh_ref, ll_ref, xw, ids, nxt, size,
                        full[2 * S + 1:3 * S + 1, :],
                        full[3 * S + 1:3 * S + 1 + kk, :],
                        full[3 * S + 1 + kk:3 * S + 1 + 2 * kk, :],
                        kk, rr)
                    amb_w = amb_w | amb
                if li == plan.l_main - 1:
                    item = win_id                    # target-type bucket
                row = win_next
            leaf = row                               # device id (1, nw)
            if item is None:                         # choose-to-device
                item = leaf
            ok = jnp.ones((1, nw), dtype=jnp.bool_)
            if not skip_rw and K:
                hh = _hash2(xw, leaf) & 0xFFFF
                w = jnp.full((1, nw), WEIGHT_ONE, dtype=jnp.int32)
                for k in range(K):                   # K <= MAX_REWEIGHT
                    w = jnp.where(leaf == jnp.int32(plan.rw_ids[k]),
                                  jnp.int32(plan.rw_w[k]), w)
                out = (w < WEIGHT_ONE) & ((w == 0) | (hh >= w))
                ok = ok & ~out
            # unfold: per-candidate (1, n) column slices (lane offsets
            # are multiples of the power-of-two PG width — relayout-
            # free) feed the shared-candidate-table slot resolution
            for i in range(nf):
                sl = slice(i * n, (i + 1) * n)
                items_c.append(item[:, sl])
                leaves_c.append(leaf[:, sl])
                ok_c.append(ok[:, sl])
                amb_any = amb_any | amb_w[:, sl]
        # slot resolution: scan the shared candidate table
        bad = jnp.zeros((1, n), dtype=jnp.bool_)
        chosen_i = []
        chosen_l = []
        for s in range(numrep):
            found = jnp.zeros((1, n), dtype=jnp.bool_)
            it_s = jnp.full((1, n), ITEM_NONE, dtype=jnp.int32)
            lf_s = jnp.full((1, n), ITEM_NONE, dtype=jnp.int32)
            for c in range(s, n_cand):
                coll = jnp.zeros((1, n), dtype=jnp.bool_)
                for pi, pl_ in zip(chosen_i, chosen_l):
                    coll = coll | (items_c[c] == pi) | (leaves_c[c] == pl_)
                good = ok_c[c] & ~coll & ~found
                it_s = jnp.where(good, items_c[c], it_s)
                lf_s = jnp.where(good, leaves_c[c], lf_s)
                found = found | good
            chosen_i.append(it_s)
            chosen_l.append(lf_s)
            bad = bad | ~found
        out_ref[...] = jnp.concatenate(chosen_l, axis=0)
        # ambiguous class-draw lanes are recomputed whole by the XLA
        # fallback, exactly like candidate-exhausted lanes
        bad_ref[...] = (bad | amb_any).astype(jnp.int32)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("plan", "numrep", "interpret"))
def _run_kernel(plan: KernelPlan, xs: jax.Array, numrep: int,
                interpret: bool = False):
    """xs (N,) int32 -> (leaves (N, numrep) int32, bad (N,) bool).

    N must be a multiple of the candidate-batched PG cell width
    (kernel_geometry(plan, numrep + SPEC_EXTRA)[0] — a power of two
    dividing plan.lanes, so any plan.lanes multiple qualifies)."""
    n = xs.shape[0]
    n_cand = numrep + SPEC_EXTRA
    LANES, fold, _groups = kernel_geometry(plan, n_cand)
    assert n % LANES == 0, (n, LANES)
    l_total = plan.l_main + plan.l_leaf
    skip_rw = plan.rw_ids.shape[0] == 0
    kernel = _make_kernel(plan, numrep, n_cand, skip_rw, fold)
    grid = (n // LANES,)
    # index maps return jnp.int32(0), not the literal 0: under the
    # caller's enable_x64 the literal traces as i64 and Mosaic cannot
    # legalize the index map's (i64, i32) func.return
    zero = lambda i: (jnp.int32(0), jnp.int32(0))
    in_specs = [pl.BlockSpec((1, LANES), lambda i: (jnp.int32(0), i))]
    operands = [xs.reshape(1, n)]
    for li, tbl in enumerate(plan.levels):
        R, P = tbl.shape
        in_specs.append(pl.BlockSpec((R, P), zero))
        operands.append(jnp.asarray(tbl))
    in_specs.append(pl.BlockSpec((256, 128), zero))
    operands.append(jnp.asarray(plan.zg2dT))
    if plan.rhlh is not None:
        in_specs.append(pl.BlockSpec((14, 129), zero))
        operands.append(jnp.asarray(plan.rhlh))
        in_specs.append(pl.BlockSpec((6, 256), zero))
        operands.append(jnp.asarray(plan.ll))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    leaves, bad = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((numrep, LANES),
                                lambda i: (jnp.int32(0), i)),
                   pl.BlockSpec((1, LANES),
                                lambda i: (jnp.int32(0), i))],
        out_shape=[jax.ShapeDtypeStruct((numrep, n), jnp.int32),
                   jax.ShapeDtypeStruct((1, n), jnp.int32)],
        interpret=interpret,
        **params,
    )(*operands)
    return leaves.T, bad[0].astype(bool)

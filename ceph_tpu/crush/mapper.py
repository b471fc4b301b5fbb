"""Vectorized CRUSH rule VM — millions of PG mappings per device step.

The TPU-native replacement for the reference's per-PG scalar walk
(ref: src/crush/mapper.c crush_do_rule and its choose loops). Design
(SURVEY.md §7): the PG id x is the vectorized lane axis; rule steps unroll
at trace time; the divergent retry loops become masked ``lax.while_loop``s
(all lanes iterate until the slowest finishes — collisions are rare, so
nearly all lanes finish in one pass); bucket descent is a fixed unroll to
the map's max depth; per-bucket variable arity is padding + masks.

Semantics deltas vs the scalar spec (``mapper_ref``), all documented:
- legacy tunables (chooseleaf_stable=0, local retries) transparently fall
  back to the scalar spec per map (data-dependent loop bounds don't
  vectorize); modern maps take the device path;
- firstn blocks are fixed-width with failure holes compacted at EMIT, which
  reproduces the scalar output except when a multi-root step underfills
  mid-rule (astronomically rare, needs a near-full cluster of failures);
- all five bucket algorithms vectorize (straw2/uniform/list/straw/tree);
- choose_args: multi-position weight-sets use position = block-relative
  slot index (upstream restarts outpos per root column, ref: crush_do_rule),
  which matches the scalar outpos except after an earlier same-block slot
  failure (upstream feeds the dynamic outpos; single-position sets — the
  balancer's output — are always exact).

The straw2 draw is 48-bit fixed point, so the draw math needs 64-bit
integers; x64 is enabled ONLY inside this module's entry points via the
scoped ``jax.enable_x64(True)`` context (round 1 flipped the global
``jax_enable_x64`` flag at import time, silently changing dtype semantics
for every other JAX user in the process). Per-lane loop state stays int32.

Large batches are tiled: ``map_pgs`` splits the x range into fixed-size
blocks (bounding the (N, S) int64 straw2 temps that OOMed round 1 at 4M
lanes), and ``sweep`` streams an arbitrary PG range through per-block
device programs with on-device utilization counts — dispatches
pipeline (async), only the final count readback synchronizes, and nothing
of O(N) ever crosses the host boundary.

Performance techniques (each cross-checked bit-exact vs mapper_ref):
- uniform-weight exact draw shortcut (round 3, the big one — 17x):
  element gathers cost ~7-9 ns/element on this platform, so the 64K
  ln-table gather the general draw then made dominated everything; for
  buckets whose items share one weight w <= the minimum positive
  crush_ln gap (~2^28.5 — every real-world bucket), draw ties are
  provably exactly the ln-equality hash pairs (ln_table.ln_gap_info),
  so the winner is argmax of the raw 16-bit hashes with an
  adjacent-pair tie repair — no ln table, no divide, no int64 (see
  _straw2_uniform_choose);
- the ln-equality predicate and other tiny-table lookups run as one-hot
  matmuls on the MXU instead of gathers (_zg_pair);
- per-bucket scalars ride ONE packed (B,1) meta word (size|alg|btype)
  row-gathered once per descent level and carried to the next;
- is_out compiles to False when every device weight is full
  (cfg["skip_is_out"], part of the jit key);
- general path (mixed weights / choose_args): crush_ln by the kernel's
  exact fixed-point ladder (129- and 256-entry tables fetched by one-hot
  matmuls, no element gather; see _straw2_choose), magic-multiply exact
  division (no 64-bit divider on TPU), speculative parallel tries
  replacing most while_loop retry iterations, and static descent-depth
  unrolling.

Mapping engine layers (round 6, mesh layer round 10): this module is
the bottom of the serving stack —
- **Mapper** (here): batched device mapping. The fused Pallas kernel
  (``pallas_mapper``) now serves arbitrary continuous per-item weights
  and single-position choose_args weight-sets: crush_ln runs as an
  exact fixed-point ladder whose RH/LH and LL tables are fetched by
  one-hot matmuls (same MXU trick as ``_zg_pair``), so a
  balancer-style weight-set no longer falls off the kernel onto the
  XLA path; the lanes the kernel flags are recomputed on the XLA
  general path by the same ladder (PERF.md §5-§6). Since
  round 15 its descent is level-major with the replica-candidate axis
  folded into the lane axis — one fused fetch+choose per level for
  ALL candidates, O(l_total) MXU ops independent of numrep
  (``kernel_plan_info`` reports the per-sweep fetch count and fold
  for bench rows).
  ``mapping_path(rule, width)`` reports which engine — pallas / xla /
  scalar — serves a given shape; bench rows record it per variant
  (and diff it against ``last_map_path``, the engine that actually
  ran, so a silent mid-run kernel degrade is a visible fact).
- **sharded sweep** (``crush/sharded_sweep.py``, round 10): the same
  per-lane programs SPMD over a device mesh — the PG batch sharded on
  the mesh axis, map tensors replicated, zero collectives on the hot
  path (one (max_devices,) psum closes the aggregated sweep).
  ``Mapper(mesh=...)``/``attach_mesh`` route batches of at least
  ``mesh_min_batch`` lanes through it; bit-exact vs the single-device
  path lane for lane, including kernel ambiguity-fallback lanes.
- **OSDMapMapping** (``osd/osdmap_mapping.py``): a full-cluster
  PG->OSD table maintained ACROSS epochs by delta remap — an
  incremental's affected-PG set is computed from the map diff and only
  those seeds re-enter the pipeline (topology changes full-sweep).
- **OSDMap epoch-keyed memo**: scalar data-path lookups (Objecter op
  targeting, mon repair, lazy PG instantiation) are memoized per
  epoch; any epoch bump drops the memo wholesale, so the cache can
  never serve across ``apply_incremental``.
"""

from __future__ import annotations

import functools
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ceph_tpu.crush import hash as h
from ceph_tpu.crush import pallas_mapper as _pm
from ceph_tpu.crush.tensors import PackedMap, pack_map
from ceph_tpu.crush.types import (
    ALG_LIST, ALG_STRAW, ALG_STRAW2, ALG_TREE, ALG_UNIFORM,
    ITEM_NONE,
    OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP, OP_CHOOSE_FIRSTN,
    OP_CHOOSE_INDEP, OP_EMIT, OP_NOOP, OP_SET_CHOOSELEAF_STABLE,
    OP_SET_CHOOSELEAF_TRIES, OP_SET_CHOOSELEAF_VARY_R,
    OP_SET_CHOOSE_LOCAL_FALLBACK_TRIES, OP_SET_CHOOSE_LOCAL_TRIES,
    OP_SET_CHOOSE_TRIES, OP_TAKE,
    CrushMap, WEIGHT_ONE,
)

S64_MIN = np.int64(np.iinfo(np.int64).min)
S64_MAX = np.int64(np.iinfo(np.int64).max)
LN_ONE = np.int64(1) << 48

# per-process Mapper incarnation tokens: the devmon compile-warmth key
# for PER-MAPPER jit wrappers (the fused-kernel fns) must be unique per
# incarnation — id(fn) is recyclable after GC and would mark a fresh
# Mapper's cold compile warm
import itertools as _itertools

_MAPPER_TOKEN = _itertools.count(1)

# Lifecycle counters (round-4, VERDICT r3 ask #10): every balancer
# iteration historically rebuilt a Mapper, and reweights can flip the
# skip_is_out jit key — this makes pack/compile traffic observable via
# `perf dump` instead of guessed. Registered process-wide like a
# daemon's counters (ref: the role of src/common/perf_counters.h).
from ceph_tpu.utils import tracing
from ceph_tpu.utils.devmon import devmon as _devmon
from ceph_tpu.utils.perf_counters import PerfCountersBuilder as _PCB

PERF = (_PCB("crush_mapper")
        .add_u64_counter("packs", "Mapper constructions (pack + staging)")
        .add_time("pack_seconds", "time spent constructing Mappers")
        .add_u64_counter("kernel_plans", "fused Pallas kernel plan builds")
        .add_u64_counter("kernel_compiles", "fused-kernel jit wrappers built")
        .add_u64_counter("kernel_exec_failures",
                         "fused-kernel compile/run failures that degraded "
                         "this Mapper to the XLA path")
        .add_u64_counter("kernel_probes",
                         "quarantine re-probe attempts (backoff-paced "
                         "kernel runs compared bit-exact vs the serving "
                         "path)")
        .add_u64_counter("kernel_repromotes",
                         "quarantined kernels re-promoted after a "
                         "bit-exact probe passed")
        .add_u64_counter("rule_compiles", "XLA rule-body jit builds")
        .add_u64_counter("sweep_compiles", "aggregated-sweep jit builds")
        .add_u64_counter("reweights", "set_device_weights calls")
        .add_u64_counter("reweight_recompiles",
                         "reweights that flipped skip_is_out (new jit key)")
        .add_u64_counter("pgs_mapped", "PG lanes through map_pgs/sweep")
        .add_u64_counter("sweep_blocks", "device blocks dispatched by sweep")
        .add_u64_counter("sweep_lanes",
                         "lanes dispatched by sweep: the sum of its "
                         "blocks' widths (pgs_mapped over it is the "
                         "fill share)")
        .add_u64_counter("kernel_take_plans",
                         "kernel plans run, one a take/emit block of "
                         "the rule, summed over the blocks of lanes "
                         "dispatched (a rule of one block counts one a "
                         "block, the docs' SSD-primary rule two); "
                         "counted on the host")
        .add_u64_counter("kernel_flagged_lanes",
                         "lanes the fused kernel flagged to the "
                         "bit-exact recompute, summed over a sweep's "
                         "blocks (over sweep_lanes the flag rate). "
                         "Counted for plans with a class or a "
                         "continuous level, whose draws decide inside "
                         "a margin; an all-uniform plan's sweep "
                         "program carries no tally")
        .add_u64_counter("kernel_fallback_blocks",
                         "sweep blocks in which the compact recompute "
                         "of flagged lanes ran (any lane flagged)")
        .add_u64_counter("kernel_fallback_overflows",
                         "sweep blocks whose flagged lanes exceeded "
                         "the fallback buffer, which then took more "
                         "than one pass of it")
        .add_u64_counter("indep_blocks",
                         "choose_indep blocks a sweep ran on the rule VM")
        .add_u64_counter("indep_rounds",
                         "rounds those blocks ran: the sum of their "
                         "final ftotal (a round is every position's "
                         "descent, at the block's full width or, once "
                         "it narrowed, at an eighth or a 128th of it)")
        .add_u64_counter("indep_lane_rounds_needed",
                         "lanes that still had a position to fill, "
                         "summed over those rounds (over indep_rounds x "
                         "the block's width it is the share of the "
                         "rule's lane-rounds that had anything to "
                         "place; over indep_lane_rounds_run the share "
                         "of the lane-rounds the device ran)")
        .add_u64_counter("indep_lane_rounds_run",
                         "the width of every round those blocks ran, "
                         "summed: the block's width for a full-width "
                         "round, an eighth or a 128th of it for a "
                         "narrow one")
        .add_u64_counter("indep_blocks_narrowed",
                         "blocks that gathered their unfilled lanes "
                         "into a block an eighth as wide and went on "
                         "with their rounds there")
        .add_u64_counter("indep_holes",
                         "positions an indep sweep emitted as ITEM_NONE")
        .add_u64_counter("firstn_slots",
                         "lane-slots the firstn blocks of a rule VM "
                         "sweep ran: each block's lanes x its slots")
        .add_u64_counter("firstn_loop_lanes",
                         "of those, the lane-slots the speculative tries "
                         "left to the retry loop")
        .add_u64_counter("firstn_loop_rounds",
                         "rounds that loop ran at the block's full "
                         "width, summed over slots")
        .create_perf_counters())


def _u32(v):
    return v.astype(jnp.uint32)


@functools.lru_cache(maxsize=1)
def _staged_const_tables():
    """The map-INDEPENDENT device tables — crush_ln's RH/LH and LL byte
    planes (the straw2 draw's ladder, ``pallas_mapper._ln_plane_tables``)
    and the zg ln-equality factorization — staged once per process.
    Every Mapper used to re-ship them on construction; each transfer
    pays a fixed latency, and the balancer rebuilds a Mapper per map
    mutation, so the constants were a standing tax on pack_seconds."""
    with jax.enable_x64(True):
        from ceph_tpu.crush.ln_table import ln_gap_info
        _, zg = ln_gap_info()
        rhlh, ll = _pm._ln_plane_tables()
        return (jnp.asarray(rhlh), jnp.asarray(ll),
                jnp.asarray(zg.reshape(256, 256), dtype=jnp.float32))


# ---------------------------------------------------------------------------
# Vectorized bucket choose
# ---------------------------------------------------------------------------

def _zg_pair(arrs, v):
    """(N,) int32 v in [0, 0xffff] -> bool: crush_ln(v) == crush_ln(v+1).

    The 64K-bit predicate is factored as a (256, 256) 0/1 table looked
    up with two 256-wide one-hot products — element gathers on this
    platform cost ~7 ns/element regardless of table size, while the
    one-hot compare + (N,256)@(256,256) f32 matmul runs on the MXU.
    """
    hi = (v >> 8) & 0xFF
    lo = v & 0xFF
    iota = jnp.arange(256, dtype=jnp.int32)
    oh_hi = (hi[:, None] == iota[None, :]).astype(jnp.float32)   # (N,256)
    rowv = jnp.dot(oh_hi, arrs["zg2d"],
                   preferred_element_type=jnp.float32)           # (N,256)
    oh_lo = (lo[:, None] == iota[None, :]).astype(jnp.float32)
    return jnp.sum(rowv * oh_lo, axis=1) > 0.5


def _straw2_uniform_choose(arrs, rows, x, r, u, posmask, items):
    """Exact uniform-weight straw2 winner from the raw 16-bit hashes.

    Licensed by ln_table.ln_gap_info: with all item weights equal to one
    w in (0, G], the post-division draw tie-set of the minimal q is
    exactly the ln-equality class of the maximal hash — which is either
    {u_max} or the adjacent pair {u_max-1, u_max}. The scalar spec picks
    the FIRST index of that set (crush keeps the incumbent on draw ties,
    ref: mapper.c bucket_straw2_choose draw > high_draw), so the winner
    is the first slot whose hash is in the class. No ln, no division.
    """
    ui = u.astype(jnp.int32)                      # values <= 0xffff
    score = jnp.where(posmask, ui, -1)
    umax = jnp.max(score, axis=1)                 # (N,)
    zg = _zg_pair(arrs, jnp.maximum(umax - 1, 0)) & (umax > 0)
    member = (ui == umax[:, None]) | \
        (zg[:, None] & (ui == (umax - 1)[:, None]))
    member = member & posmask
    # first-member select WITHOUT a per-lane gather (take_along_axis
    # costs ~11 ms per call at 786K lanes on this platform): the first
    # true slot is where the running count first hits 1.
    first = member & (jnp.cumsum(member.astype(jnp.int32), axis=1) == 1)
    return jnp.sum(jnp.where(first, items, 0), axis=1, dtype=jnp.int32)


# hashes of a draw's (N, S) plane that the ln ladder takes at once: its
# one-hot fetches leave 20 int32 planes an element in HBM (80 bytes), so
# a wider plane (the rule VM's blocks: 2^17 lanes x 6 tries x 32 slots
# by its own sizing) goes through in rows of this many, 21 MB a row
_LN_ROW = 1 << 18


def _straw2_neg(arrs, u):
    """(N, S) int32 hashes in [0, 0xffff] -> (N, S) uint64
    2^48 - crush_ln(u), bit-exact, by the kernel's ladder over the
    plane flattened to rows of at most ``_LN_ROW``."""
    def row(v):
        nh, nl = _pm._crush_ln_neg(arrs["ln_rhlh"], arrs["ln_ll"], v[None])
        return (nh[0].astype(jnp.uint64) << jnp.uint64(24)) \
            | nl[0].astype(jnp.uint64)

    flat = u.reshape(-1)
    n = flat.shape[0]
    if n <= _LN_ROW:
        return row(flat).reshape(u.shape)
    rows = jnp.pad(flat, (0, -n % _LN_ROW)).reshape(-1, _LN_ROW)
    return lax.map(row, rows).reshape(-1)[:n].reshape(u.shape)


def _straw2_choose(arrs, rows, x, r, pos=None, cfg=None, size=None):
    """(N,) lanes: straw2 argmax draw (ref: mapper.c bucket_straw2_choose).

    The negated draw numerator neg = 2^48 - crush_ln(u) of every (lane,
    slot) comes from the kernel's own exact ladder
    (``pallas_mapper._crush_ln_neg``) run over the flattened (N, S)
    plane: a bit-length normalize, RH/LH and LL fetched by 129- and
    256-entry one-hot matmuls (XLA fuses the compare into the dot, so
    no one-hot reaches HBM) and int32 limb arithmetic. It replaced a
    gather from a 64K-entry table, which on a v5e cost 7.1 ns an element
    and half of a weight-set sweep's device time (PERF.md §5).

    pos: (N,) replica positions, consulted only when a choose_args
    weight-set is packed (arrs["cw"]): position p draws with
    weight_set[min(p, P-1)] (out-of-range clamps to the last set, like
    mapper.c get_choose_arg_weights) and the override ids.
    """
    items = arrs["items"][rows]            # (N, S) int32
    if size is None:
        size = arrs["size_c"][rows][:, 0]  # (N,) via (B,1) row gather
    S = items.shape[1]
    if cfg is not None and cfg.get("all_uniform") and "cw" not in arrs:
        # Every straw2 bucket on this map qualifies for the exact
        # uniform-weight shortcut: skip the negln gather, the 64-bit
        # magic divide, and the int64 argmin entirely.
        u = (h.hash32_3(_u32(x)[:, None], _u32(items), _u32(r)[:, None],
                        xp=jnp) & jnp.uint32(0xFFFF))
        posmask = jnp.arange(S, dtype=jnp.int32)[None, :] < size[:, None]
        return _straw2_uniform_choose(arrs, rows, x, r, u, posmask, items)
    if "cw" in arrs:
        P = arrs["cw"].shape[0]
        # out-of-range positions clamp to the last set (ref: mapper.c
        # get_choose_arg_weights)
        p = jnp.clip(pos, 0, P - 1).astype(jnp.int32) \
            if (pos is not None and P > 1) else jnp.zeros_like(rows)
        w = arrs["cw"][p, rows]
        hash_ids = arrs["cids"][rows]
        m1 = arrs["cm1"][p, rows]
        m0 = arrs["cm0"][p, rows]
        sh = arrs["csh"][p, rows]
    else:
        w = arrs["weights"][rows]          # (N, S) int64
        hash_ids = items
        m1 = arrs["wm1"][rows]
        m0 = arrs["wm0"][rows]
        sh = arrs["wsh"][rows]
    u = (h.hash32_3(_u32(x)[:, None], _u32(hash_ids), _u32(r)[:, None],
                    xp=jnp) & jnp.uint32(0xFFFF)).astype(jnp.int32)
    neg = _straw2_neg(arrs, u)                  # (N, S), <= 2^48
    # draw = trunc((ln - 2^48)/w) = -(neg // w); maximize draw = minimize q.
    # neg // w via the per-slot magic multiply (exact; see PackedMap.wm1)
    # — TPUs have no 64-bit divider and XLA's emulation is ~6.5x slower.
    n1 = neg >> jnp.uint64(32)
    n0 = neg & jnp.uint64(0xFFFFFFFF)
    mid = n1 * m0 + n0 * m1 + ((n0 * m0) >> jnp.uint64(32))
    q = ((n1 * m1 + (mid >> jnp.uint64(32))) >> sh).astype(jnp.int64)
    # w in {1, 2}: plain shift (magic table is zero there); w <= 0: masked
    small = w < 3
    q = jnp.where(small, (neg >> jnp.clip(w - 1, 0, 1).astype(jnp.uint64)
                          ).astype(jnp.int64), q)
    posmask = jnp.arange(S, dtype=jnp.int32)[None, :] < size[:, None]
    q = jnp.where(posmask & (w > 0), q, S64_MAX)
    idx = jnp.argmin(q, axis=1)            # first min == scalar's first max
    return jnp.take_along_axis(items, idx[:, None], axis=1)[:, 0]


def _uniform_choose(arrs, rows, x, r):
    """(N,) lanes: pseudo-random permutation pick
    (ref: mapper.c bucket_perm_choose), as a full Fisher-Yates unroll."""
    items = arrs["items"][rows]
    size = arrs["size"][rows].astype(jnp.int32)
    bid = arrs["bid"][rows]
    S = items.shape[1]
    safe_size = jnp.maximum(size, 1)
    pr = (r.astype(jnp.int32) % safe_size).astype(jnp.int32)
    perm = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                            items.shape)
    ar = jnp.arange(S, dtype=jnp.int32)[None, :]
    for p in range(S - 1):
        active = (p < size - 1)
        mod = jnp.maximum(size - p, 1).astype(jnp.uint32)
        i = (h.hash32_3(_u32(x), _u32(bid), jnp.uint32(p), xp=jnp)
             % mod).astype(jnp.int32)
        idx = p + i                                     # (N,)
        val_p = perm[:, p]
        val_i = jnp.take_along_axis(perm, idx[:, None], axis=1)[:, 0]
        swap_to_p = (ar == p) & active[:, None]
        swap_to_i = (ar == idx[:, None]) & active[:, None]
        perm = jnp.where(swap_to_i, val_p[:, None],
                         jnp.where(swap_to_p, val_i[:, None], perm))
    s = jnp.take_along_axis(perm, pr[:, None], axis=1)[:, 0]
    return jnp.take_along_axis(items, s[:, None], axis=1)[:, 0]


def _list_choose(arrs, rows, x, r):
    """(N,) lanes: list bucket walk tail->head
    (ref: mapper.c bucket_list_choose)."""
    items = arrs["items"][rows]
    w = arrs["weights"][rows]
    cumw = arrs["cumw"][rows]
    size = arrs["size"][rows]
    S = items.shape[1]
    draw = h.hash32_4(_u32(x)[:, None], _u32(items), _u32(r)[:, None],
                      _u32(arrs["bid"][rows])[:, None],
                      xp=jnp).astype(jnp.int64) & 0xFFFF
    scaled = (draw * cumw) >> 16
    posmask = jnp.arange(S)[None, :] < size[:, None]
    accept = (scaled < w) & posmask
    # First acceptance scanning from the tail == highest accepting index.
    rev = accept[:, ::-1]
    idx = (S - 1) - jnp.argmax(rev, axis=1)
    found = jnp.any(accept, axis=1)
    idx = jnp.where(found, idx, 0)
    return jnp.take_along_axis(items, idx[:, None], axis=1)[:, 0]


def _straw_choose(arrs, rows, x, r):
    """(N,) lanes: legacy straw(v1) — draw = hash16 * straw_i, first max
    (ref: mapper.c bucket_straw_choose; straws from crush_calc_straw)."""
    items = arrs["items"][rows]
    straws = arrs["straws"][rows]          # (N, S) uint64
    size = arrs["size"][rows]
    S = items.shape[1]
    u = (h.hash32_3(_u32(x)[:, None], _u32(items), _u32(r)[:, None],
                    xp=jnp) & jnp.uint32(0xFFFF)).astype(jnp.uint64)
    draw = u * straws
    posmask = jnp.arange(S, dtype=jnp.int32)[None, :] < size[:, None]
    draw = jnp.where(posmask, draw, jnp.uint64(0))
    idx = jnp.argmax(draw, axis=1)         # first max, like the scalar
    return jnp.take_along_axis(items, idx[:, None], axis=1)[:, 0]


def _tree_choose(arrs, cfg, rows, x, r):
    """(N,) lanes: tree-bucket binary descent (ref: mapper.c
    bucket_tree_choose). Unrolls tree_depth_max levels; terminal (odd)
    lanes hold their node."""
    nodes = arrs["tree_nodes"]             # (B, NT) int64
    items = arrs["items"][rows]
    NT = nodes.shape[1]
    n = (arrs["tree_num"][rows] >> 1).astype(jnp.int32)   # per-lane root
    for _ in range(cfg.get("tree_depth", 0)):
        term = (n & 1) == 1
        safe_n = jnp.clip(n, 0, NT - 1)
        w = nodes[rows, safe_n].astype(jnp.uint64)
        t = (h.hash32_4(_u32(x), _u32(n), _u32(r),
                        _u32(arrs["bid"][rows]), xp=jnp)
             .astype(jnp.uint64) * w) >> jnp.uint64(32)
        half = (n & -n) >> 1
        left = n - half
        wl = nodes[rows, jnp.clip(left, 0, NT - 1)].astype(jnp.uint64)
        n_next = jnp.where(t < wl, left, n + half)
        n = jnp.where(term, n, n_next)
    leaf_slot = jnp.clip(n >> 1, 0, items.shape[1] - 1)
    return jnp.take_along_axis(items, leaf_slot[:, None], axis=1)[:, 0]


def _bucket_choose(arrs, cfg, rows, x, r, pos=None, size=None):
    """Dispatch on bucket alg (ref: mapper.c crush_bucket_choose)."""
    present = cfg["present"]
    item = _straw2_choose(arrs, rows, x, r, pos, cfg=cfg, size=size)
    if present == (ALG_STRAW2,):
        return item
    alg = arrs["alg_c"][rows][:, 0]
    if ALG_UNIFORM in present:
        item = jnp.where(alg == ALG_UNIFORM,
                         _uniform_choose(arrs, rows, x, r), item)
    if ALG_LIST in present:
        item = jnp.where(alg == ALG_LIST,
                         _list_choose(arrs, rows, x, r), item)
    if ALG_STRAW in present:
        item = jnp.where(alg == ALG_STRAW,
                         _straw_choose(arrs, rows, x, r), item)
    if ALG_TREE in present:
        item = jnp.where(alg == ALG_TREE,
                         _tree_choose(arrs, cfg, rows, x, r), item)
    return item


def _is_out(arrs, item, x, cfg=None):
    """ref: mapper.c is_out — probabilistic reweight rejection.

    Compiled out entirely (constant False) when every device weight is
    full — the common healthy-cluster case — via cfg["skip_is_out"];
    the flag is part of the jit key, so reweighting recompiles once.
    """
    devw = arrs["devw_c"]                  # (D, 1) int64
    if cfg is not None and cfg.get("skip_is_out"):
        return jnp.zeros(item.shape, dtype=bool) | (item >= devw.shape[0])
    safe = jnp.clip(item, 0, devw.shape[0] - 1)
    w = devw[safe][:, 0]
    hh = h.hash32_2(_u32(x), _u32(item), xp=jnp).astype(jnp.int64) & 0xFFFF
    out = jnp.where(w >= WEIGHT_ONE, False,
                    jnp.where(w == 0, True, hh >= w))
    return jnp.where(item >= devw.shape[0], True, out)


# ---------------------------------------------------------------------------
# Descent through the hierarchy
# ---------------------------------------------------------------------------

def _descend(arrs, cfg, start_rows, start_valid, x, base_r, ftotal,
             target_type, indep_numrep, levels: int | None = None,
             pos=None):
    """Walk from start buckets down to an item of target_type.

    base_r: (N,) int32 = rep + parent_r. ftotal: (N,) or scalar retry count.
    indep_numrep: None for firstn (r = base_r + ftotal) else the numrep used
    for the indep r-stride (ref: crush_choose_indep r computation; the
    stride consults the alg/size of the bucket at EACH level).
    levels: exact unroll count when the caller knows the static descent
    depth (uniform-depth hierarchies; see PackedMap.type_depth) — the
    max_depth default costs a full bucket_choose per excess level for
    every lane.
    Returns (item, success, r_final) — r_final is the r used at the level
    where the item was drawn (the scalar code's `r` at recursion time).
    Lanes that hit a device/bucket of the wrong kind, an empty bucket, or
    exceed the unrolled depth fail.
    """
    B = arrs["size"].shape[0]
    n = start_rows.shape[0]
    cur = jnp.clip(start_rows, 0, B - 1)
    done = ~start_valid
    success = jnp.zeros(n, dtype=bool)
    out_item = jnp.full(n, ITEM_NONE, dtype=jnp.int32)
    r_final = jnp.zeros(n, dtype=jnp.int32)
    if levels is None or not (0 < levels <= cfg["max_depth"]):
        levels = cfg["max_depth"]
    # One meta-word row gather per level: the child's meta (for its
    # type test) IS the next level's meta, so it is carried instead of
    # re-gathered, and the bucket size rides into _bucket_choose instead
    # of a second per-lane gather there.
    meta = arrs["meta_c"][cur][:, 0]
    for _ in range(levels):
        active = ~done
        size_c = meta & 0xFFFF
        if indep_numrep is None:
            r = base_r + ftotal
        else:
            alg_c = (meta >> 16) & 0xF
            stride = jnp.where(
                (alg_c == ALG_UNIFORM) & (size_c % indep_numrep == 0),
                indep_numrep + 1, indep_numrep)
            r = base_r + stride * ftotal
        item = _bucket_choose(arrs, cfg, cur, x, r, pos, size=size_c)
        empty = size_c == 0
        row = -1 - item
        is_bucket = item < 0
        child_meta = arrs["meta_c"][jnp.clip(row, 0, B - 1)][:, 0]
        it_type = jnp.where(is_bucket, child_meta >> 20, 0)
        reached = (~empty) & (it_type == target_type)
        descend_more = (~empty) & (~reached) & is_bucket & (row < B)
        fail_now = active & ~reached & ~descend_more
        out_item = jnp.where(active & reached, item, out_item)
        r_final = jnp.where(active & reached, r.astype(jnp.int32), r_final)
        success = success | (active & reached)
        done = done | (active & (reached | fail_now))
        cur = jnp.where(active & descend_more, jnp.clip(row, 0, B - 1), cur)
        meta = jnp.where(active & descend_more, child_meta, meta)
    return out_item, success, r_final


# ---------------------------------------------------------------------------
# choose_firstn / choose_indep, one replica slot at a time
# ---------------------------------------------------------------------------

def _leaf_choose(arrs, cfg, item, item_ok, x, sub_r, prior_leaves, tries,
                 pos=None):
    """The chooseleaf recursion: pick one device under `item`
    (ref: crush_choose_firstn recursive call with numrep=1, stable=1).

    Returns (leaf, ok). Device items pass through unchecked (the scalar
    code only is_out-checks items at the level whose type is 0).
    """
    n = item.shape[0]
    B = arrs["size"].shape[0]
    is_bucket = item < 0
    rows = jnp.clip(-1 - item, 0, B - 1)

    def cond(c):
        return jnp.any(~c["done"])

    def body(c):
        active = ~c["done"]
        item_l, ok, _ = _descend(arrs, cfg, rows, is_bucket & item_ok, x,
                                 sub_r, c["ftotal"], 0, None,
                                 levels=cfg.get("levels_leaf"), pos=pos)
        collide = jnp.zeros(n, dtype=bool)
        if prior_leaves is not None and prior_leaves.shape[1]:
            collide = jnp.any(item_l[:, None] == prior_leaves, axis=1)
        reject = ~ok | collide | _is_out(arrs, item_l, x, cfg)
        succeed = active & ~reject
        ftotal_next = c["ftotal"] + 1
        give_up = active & reject & (ftotal_next >= tries)
        return {
            "leaf": jnp.where(succeed, item_l, c["leaf"]),
            "ok": c["ok"] | succeed,
            "done": c["done"] | succeed | give_up,
            "ftotal": jnp.where(active & reject, ftotal_next, c["ftotal"]),
        }

    init = {
        "leaf": jnp.full(n, ITEM_NONE, dtype=jnp.int32),
        "ok": jnp.zeros(n, dtype=bool),
        "done": ~(is_bucket & item_ok),
        "ftotal": jnp.zeros(n, dtype=jnp.int32),
    }
    out = lax.while_loop(cond, body, init)
    # Device item (or failed outer) passes through.
    leaf = jnp.where(is_bucket, out["leaf"], item)
    ok = jnp.where(is_bucket, out["ok"], item_ok)
    return leaf, ok


def _choose_one_firstn(arrs, cfg, root_rows, root_valid, x, rep,
                       prior_out, prior_leaves, target_type,
                       recurse_to_leaf, tries, recurse_tries, vary_r,
                       ftotal0: int = 0, pos: int = 0,
                       narrow: tuple[int, ...] = (), tally: bool = False):
    """One replica slot of crush_choose_firstn, all lanes at once.

    ftotal0 > 0 resumes after the caller's speculative tries: the while
    cond is False when no lane is active, so the fallback costs nothing
    on collision-free blocks.

    A round is a descent of every lane, and the loop goes round while
    any lane has a try to make: at one width a slot costs its
    unluckiest lane's tries. ``narrow``: widths, widest first, at which
    the loop goes on once no more lanes than that have a try left (the
    kernel's flagged-lane recompute, ``Mapper._make_kernel_body``): the
    lanes are gathered into a block that wide and put back where they
    came from, as ``_choose_indep_block`` does. ``ftotal`` is a lane's
    own, so its ``r`` is what it was; only its company changes.

    ``tally`` (the rule VM's sweep, which does not narrow): also return
    the int32 pair (lanes that entered the loop, rounds it ran), the
    ``FIRSTN_TALLY``; without it the program is the one it was."""
    def rounds(root_rows, root_valid, x, base_r, prior_out, prior_leaves,
               c, stop_at):
        """Rounds at the width of ``x`` while more than ``stop_at`` of
        its lanes have a try to make."""
        n = x.shape[0]

        def cond(c):
            if not stop_at:
                return jnp.any(~c["done"])
            return (~c["done"]).sum(dtype=jnp.int32) > stop_at

        def body(c):
            active = ~c["done"]
            pos_v = jnp.full(n, pos, dtype=jnp.int32)
            item, ok, r_fin = _descend(arrs, cfg, root_rows, root_valid, x,
                                       base_r, c["ftotal"], target_type,
                                       None, levels=cfg.get("levels_main"),
                                       pos=pos_v)
            collide = jnp.zeros(n, dtype=bool)
            if prior_out.shape[1]:
                collide = jnp.any(item[:, None] == prior_out, axis=1)
            ok = ok & ~collide
            if recurse_to_leaf:
                r_cur = base_r + c["ftotal"]
                if vary_r:
                    sub_r = r_cur >> (vary_r - 1)
                else:
                    sub_r = jnp.zeros_like(r_cur)
                leaf, ok = _leaf_choose(arrs, cfg, item, ok, x, sub_r,
                                        prior_leaves, recurse_tries,
                                        pos=pos_v)
            else:
                leaf = item
                if target_type == 0:
                    ok = ok & ~_is_out(arrs, item, x, cfg)
            succeed = active & ok
            ftotal_next = c["ftotal"] + 1
            give_up = active & ~ok & (ftotal_next >= tries)
            out = {
                "item": jnp.where(succeed, item, c["item"]),
                "leaf": jnp.where(succeed, leaf, c["leaf"]),
                "ok": c["ok"] | succeed,
                "done": c["done"] | succeed | give_up,
                "ftotal": jnp.where(active & ~ok, ftotal_next, c["ftotal"]),
            }
            if tally:
                out["rounds"] = c["rounds"] + 1
            return out

        return lax.while_loop(cond, body, c)

    def finish(root_rows, root_valid, x, base_r, prior_out, prior_leaves,
               c, caps):
        """The rounds left to a block of ``x``'s width: there while
        more than ``caps[0]`` of its lanes have a try to make, then in
        a block that wide."""
        c = rounds(root_rows, root_valid, x, base_r, prior_out,
                   prior_leaves, c, caps[0] if caps else 0)
        if not caps:
            return c

        def go_on(c):
            # top_k, not jnp.nonzero under a cond (see _kernel_body's
            # fallback): the lanes with a try to make, then lanes that
            # are done, which stay as they are and are put back so
            _, idx = lax.top_k((~c["done"]).astype(jnp.int32), caps[0])
            sub = finish(root_rows[idx], root_valid[idx], x[idx],
                         base_r[:caps[0]], prior_out[idx], prior_leaves[idx],
                         {k: v[idx] for k, v in c.items()}, caps[1:])
            return {k: v.at[idx].set(sub[k]) for k, v in c.items()}

        return lax.cond(jnp.any(~c["done"]), go_on, lambda c: c, c)

    n = x.shape[0]
    base_r = jnp.full(n, rep, dtype=jnp.int32)
    init = {
        "item": jnp.full(n, ITEM_NONE, dtype=jnp.int32),
        "leaf": jnp.full(n, ITEM_NONE, dtype=jnp.int32),
        "ok": jnp.zeros(n, dtype=bool),
        "done": ~root_valid if ftotal0 < tries
        else jnp.ones(n, dtype=bool),
        "ftotal": jnp.full(n, ftotal0, dtype=jnp.int32),
    }
    if tally:
        init["rounds"] = jnp.int32(0)
    out = finish(root_rows, root_valid, x, base_r, prior_out, prior_leaves,
                 init, tuple(w for w in narrow if w < n))
    if tally:
        return out["item"], out["leaf"], out["ok"], jnp.stack(
            [(~init["done"]).sum(dtype=jnp.int32), out["rounds"]])
    return out["item"], out["leaf"], out["ok"]


SPEC_TRIES = 2  # speculative parallel tries per replica slot (try 0
                # succeeds for all but ~1e-3 of lanes on healthy maps; the
                # while_loop fallback catches the tail exactly)


def _leaf_once(arrs, cfg, item, item_ok, x, sub_r, pos=None):
    """Single-pass chooseleaf recursion (descend_once semantics): one
    descent from `item` to a device; no retry loop. Device items pass
    through unchecked (the scalar code only is_out-checks at type 0)."""
    B = arrs["size"].shape[0]
    is_bucket = item < 0
    rows = jnp.clip(-1 - item, 0, B - 1)
    leaf, ok, _ = _descend(arrs, cfg, rows, is_bucket & item_ok, x,
                           sub_r, jnp.zeros_like(sub_r), 0, None,
                           levels=cfg.get("levels_leaf"), pos=pos)
    leaf = jnp.where(is_bucket, leaf, item)
    ok = jnp.where(is_bucket, ok, item_ok)
    return leaf, ok


def _choose_firstn_block(arrs, cfg, root_rows, root_valid, x, numrep,
                         target_type, recurse_to_leaf, tries, recurse_tries,
                         vary_r, pos_base: int = 0, tally: bool = False):
    """numrep replica slots from one root column -> (N, numrep) x2, and
    with ``tally`` the block's ``FIRSTN_TALLY`` (int32: lane-slots run,
    lane-slots the speculative tries left to the loop, the loop's
    rounds summed over slots).

    Structure (round 2): the first SPEC_TRIES tries of EVERY slot descend
    in parallel as extra lanes — the descent for (slot, try) is
    deterministic (r = slot + try under chooseleaf_stable=1) and
    independent of which earlier tries succeed, so speculation is exact.
    Collision filtering against earlier slots is a cheap elementwise scan
    afterwards. Only lanes whose slot fails all SPEC_TRIES enter the
    masked while_loop fallback (round 1 ran that full-width loop for
    every slot: ~5-7 full-width re-descents per block for a handful of
    colliding lanes).

    The speculative path requires the single-descent leaf recursion
    (recurse_tries == 1, the chooseleaf_descend_once=1 modern default);
    other configurations use the loop path.
    """
    n = x.shape[0]
    out = jnp.full((n, numrep), ITEM_NONE, dtype=jnp.int32)
    leaves = jnp.full((n, numrep), ITEM_NONE, dtype=jnp.int32)
    speculate = (tries >= 1) and (recurse_tries == 1 or not recurse_to_leaf)

    items_s = ok_s = leaves_s = None
    if speculate:
        K = min(SPEC_TRIES, tries)
        # lanes (n, numrep*K): slot-major, try-minor
        reps = np.repeat(np.arange(numrep, dtype=np.int32), K)
        ts = np.tile(np.arange(K, dtype=np.int32), numrep)
        r_all = jnp.asarray(reps + ts, dtype=jnp.int32)      # r = slot+ftotal
        M = numrep * K
        x_f = jnp.broadcast_to(x[:, None], (n, M)).reshape(-1)
        rows_f = jnp.broadcast_to(root_rows[:, None], (n, M)).reshape(-1)
        valid_f = jnp.broadcast_to(root_valid[:, None], (n, M)).reshape(-1)
        base_r = jnp.broadcast_to(r_all[None, :], (n, M)).reshape(-1)
        ftot0 = jnp.zeros_like(base_r)
        pos_f = jnp.broadcast_to(
            jnp.asarray(reps + pos_base, dtype=jnp.int32)[None, :],
            (n, M)).reshape(-1)
        item_f, ok_f, _ = _descend(arrs, cfg, rows_f, valid_f, x_f,
                                   base_r, ftot0, target_type, None,
                                   levels=cfg.get("levels_main"), pos=pos_f)
        if recurse_to_leaf:
            if vary_r:
                sub_r = base_r >> (vary_r - 1)
            else:
                sub_r = jnp.zeros_like(base_r)
            leaf_f, ok_f = _leaf_once(arrs, cfg, item_f, ok_f, x_f, sub_r,
                                      pos=pos_f)
            # is_out applies to recursed leaves only; a device item sitting
            # directly at the target level passes through unchecked (same
            # as the loop path / scalar spec).
            ok_f = ok_f & ~(_is_out(arrs, leaf_f, x_f, cfg) & (item_f < 0))
        else:
            leaf_f = item_f
            if target_type == 0:
                ok_f = ok_f & ~_is_out(arrs, item_f, x_f, cfg)
        items_s = item_f.reshape(n, numrep, K)
        ok_s = ok_f.reshape(n, numrep, K)
        leaves_s = leaf_f.reshape(n, numrep, K)

    loop = []                            # the tally's (lanes, rounds) a slot
    for rep in range(numrep):
        if speculate:
            K = items_s.shape[2]
            it_k = items_s[:, rep, :]                        # (n, K)
            lf_k = leaves_s[:, rep, :]
            ok_k = ok_s[:, rep, :]
            if rep:
                collide = jnp.any(
                    it_k[:, :, None] == out[:, None, :rep], axis=2)
                ok_k = ok_k & ~collide
                if recurse_to_leaf:
                    lcollide = jnp.any(
                        lf_k[:, :, None] == leaves[:, None, :rep], axis=2)
                    ok_k = ok_k & ~lcollide
            first = jnp.argmax(ok_k, axis=1)                 # first valid try
            any_ok = jnp.any(ok_k, axis=1)
            item = jnp.take_along_axis(it_k, first[:, None], axis=1)[:, 0]
            leaf = jnp.take_along_axis(lf_k, first[:, None], axis=1)[:, 0]
            # fallback continues from ftotal = K for unresolved lanes only
            item2, leaf2, ok2, *slot = _choose_one_firstn(
                arrs, cfg, root_rows, root_valid & ~any_ok, x, rep,
                out[:, :rep], leaves[:, :rep], target_type,
                recurse_to_leaf, tries, recurse_tries, vary_r,
                ftotal0=K, pos=pos_base + rep, tally=tally)
            ok = any_ok | ok2
            item = jnp.where(any_ok, item, item2)
            leaf = jnp.where(any_ok, leaf, leaf2)
        else:
            item, leaf, ok, *slot = _choose_one_firstn(
                arrs, cfg, root_rows, root_valid, x, rep,
                out[:, :rep], leaves[:, :rep], target_type,
                recurse_to_leaf, tries, recurse_tries, vary_r,
                pos=pos_base + rep, tally=tally)
        loop += slot
        out = out.at[:, rep].set(jnp.where(ok, item, ITEM_NONE))
        leaves = leaves.at[:, rep].set(jnp.where(ok, leaf, ITEM_NONE))
    if tally:
        return out, leaves, jnp.concatenate(
            [jnp.full(1, n * max(numrep, 0), dtype=jnp.int32),
             sum(loop, jnp.zeros(2, dtype=jnp.int32))])
    return out, leaves


def _leaf_choose_indep(arrs, cfg, item, item_ok, x, parent_r, rep, numrep,
                       tries, pos=None):
    """Indep leaf recursion (ref: crush_choose_indep recursive call with
    left=1, outpos=rep, parent_r=r)."""
    n = item.shape[0]
    B = arrs["size"].shape[0]
    is_bucket = item < 0
    rows = jnp.clip(-1 - item, 0, B - 1)
    base_r = rep + parent_r

    def cond(c):
        return jnp.any(~c["done"])

    def body(c):
        active = ~c["done"]
        item_l, ok, _ = _descend(arrs, cfg, rows, is_bucket & item_ok, x,
                                 base_r, c["ftotal"], 0, numrep,
                                 levels=cfg.get("levels_leaf"), pos=pos)
        reject = ~ok | _is_out(arrs, item_l, x, cfg)
        succeed = active & ~reject
        ftotal_next = c["ftotal"] + 1
        give_up = active & reject & (ftotal_next >= tries)
        return {
            "leaf": jnp.where(succeed, item_l, c["leaf"]),
            "ok": c["ok"] | succeed,
            "done": c["done"] | succeed | give_up,
            "ftotal": jnp.where(active & reject, ftotal_next, c["ftotal"]),
        }

    init = {
        "leaf": jnp.full(n, ITEM_NONE, dtype=jnp.int32),
        "ok": jnp.zeros(n, dtype=bool),
        "done": ~(is_bucket & item_ok),
        "ftotal": jnp.zeros(n, dtype=jnp.int32),
    }
    out = lax.while_loop(cond, body, init)
    leaf = jnp.where(is_bucket, out["leaf"], item)
    ok = jnp.where(is_bucket, out["ok"], item_ok)
    return leaf, ok


def _choose_indep_block(arrs, cfg, root_rows, root_valid, x, out_size,
                        numrep, target_type, recurse_to_leaf, tries,
                        recurse_tries, pos_base: int = 0):
    """ref: mapper.c crush_choose_indep -- position-stable EC placement.

    A round runs every position's descent at the width it is given, and
    a block goes round again while any lane has a position unfilled.
    The rounds run at the block's full width ``n`` while more than
    ``n >> 3`` lanes are unfilled (round 1 always; on a healthy map
    nothing after it: 11 positions over 640 hosts leave 8.4%). Then the
    unfilled lanes are gathered into a block an eighth as wide, the
    rounds go on there, and once no more than ``n >> 7`` are left
    (0.12% after round 2 there) in one that wide; the rows are put back
    where they came from. The round counter goes on where it stood: a
    lane still unfilled has been in every round, so ``r`` is what
    upstream's ``ftotal`` gives it, and only its company changes. A
    block under ``MIN_NARROW_WIDTH`` is the one loop at full width.

    On a v5e a round of 11 positions takes 435 ms at 2^20 lanes, 69 at
    2^17 with the gathers into it, 3.5 at 2^13, and gathering and
    putting back cost 37 ms at 2^20 (PERF.md, PR 34): the 10,240-OSD
    map's four rounds took 1.76 s at full width and take 0.57 s.

    Returns (out, leaves, tally), ``tally`` int32: the final ftotal;
    the lanes that still had a position to fill summed over the rounds
    (the one reduce a round, which also ends the loops); the widths of
    the rounds summed; 1 if the block narrowed."""
    n = x.shape[0]
    UNDEF = ITEM_NONE - 1

    def unfilled(out):
        return jnp.any(out == UNDEF, axis=1)

    def rounds(root_rows, root_valid, x, carry, stop_at):
        """Rounds at the width of ``x`` while more than ``stop_at`` of
        its lanes are unfilled."""
        w = x.shape[0]
        # The full-width round keeps its positions unrolled; a narrow
        # one runs them as a loop. With unrolled narrow copies the 2^20
        # sweep program was 231 MB of code against 119 and a process
        # loaded it from the cache in 20.7 s against 7.5; as loops they
        # make it 134 MB and 8.4 s, for 8-12 ms more a sweep. The
        # full-width round as a loop takes 530 ms, not 440 (PERF.md).
        unrolled = w == n

        def cond(c):
            return (c["ftotal"] < tries) & (c["left"] > stop_at)

        def body(c):
            ftotal = c["ftotal"]

            def place(rep, placed):
                """Position ``rep``'s descent for the lanes that need it."""
                out, leaves = placed
                col = lax.dynamic_index_in_dim(out, rep, 1, keepdims=False)
                need = col == UNDEF
                base_r = jnp.full(w, rep, dtype=jnp.int32)
                pos_v = jnp.full(w, pos_base + rep, dtype=jnp.int32)
                item, ok, r_parent = _descend(
                    arrs, cfg, root_rows, root_valid & need, x, base_r,
                    ftotal, target_type, numrep,
                    levels=cfg.get("levels_main"), pos=pos_v)
                real = jnp.where(out == UNDEF, ITEM_NONE, out)
                collide = jnp.any(item[:, None] == real, axis=1)
                ok = ok & ~collide
                if recurse_to_leaf:
                    # parent_r = the r at which `item` was drawn (scalar
                    # passes its loop-local r into the recursion).
                    leaf, ok = _leaf_choose_indep(
                        arrs, cfg, item, ok, x, r_parent, rep, numrep,
                        recurse_tries, pos=pos_v)
                else:
                    leaf = item
                    if target_type == 0:
                        ok = ok & ~_is_out(arrs, item, x, cfg)
                ok = need & ok
                lcol = lax.dynamic_index_in_dim(leaves, rep, 1,
                                                keepdims=False)
                return (lax.dynamic_update_index_in_dim(
                            out, jnp.where(ok, item, col), rep, 1),
                        lax.dynamic_update_index_in_dim(
                            leaves, jnp.where(ok, leaf, lcol), rep, 1))

            placed = c["out"], c["leaves"]
            if unrolled:
                for rep in range(out_size):
                    placed = place(rep, placed)
            else:
                placed = lax.fori_loop(0, out_size, place, placed)
            out, leaves = placed
            return {**c, "out": out, "leaves": leaves, "ftotal": ftotal + 1,
                    "left": unfilled(out).sum(dtype=jnp.int32),
                    "needed": c["needed"] + c["left"],
                    "run": c["run"] + w}

        return lax.while_loop(cond, body, carry)

    def finish(root_rows, root_valid, x, c, caps):
        """The rounds left to a block of ``x``'s width: there while
        more than ``caps[0]`` of its lanes are unfilled, then in a
        block that wide."""
        c = rounds(root_rows, root_valid, x, c, caps[0] if caps else 0)
        if not caps:
            return c

        def narrow(c):
            # top_k, not jnp.nonzero under a cond (see _kernel_body's
            # fallback): the unfilled lanes, then lanes already filled,
            # whose ``need`` is false in every position: they place
            # nothing and put back what they held
            _, idx = lax.top_k(unfilled(c["out"]).astype(jnp.int32),
                               caps[0])
            sub = finish(root_rows[idx], root_valid[idx], x[idx],
                         {**c, "out": c["out"][idx],
                          "leaves": c["leaves"][idx]}, caps[1:])
            return {**sub, "narrowed": jnp.int32(1),
                    "out": c["out"].at[idx].set(sub["out"]),
                    "leaves": c["leaves"].at[idx].set(sub["leaves"])}

        return lax.cond((c["left"] > 0) & (c["ftotal"] < tries), narrow,
                        lambda c: c, c)

    out0 = jnp.full((n, out_size), UNDEF, dtype=jnp.int32)
    c = finish(root_rows, root_valid, x,
               {"out": out0, "leaves": out0, "ftotal": jnp.int32(0),
                "left": unfilled(out0).sum(dtype=jnp.int32),
                "needed": jnp.int32(0), "run": jnp.int32(0),
                "narrowed": jnp.int32(0)}, narrow_widths(n))
    out = jnp.where(c["out"] == UNDEF, ITEM_NONE, c["out"])
    leaves = jnp.where(c["leaves"] == UNDEF, ITEM_NONE, c["leaves"])
    return out, leaves, jnp.stack([c[k] for k in
                                   ("ftotal", "needed", "run", "narrowed")])


def _compact(w):
    """Stable left-compaction of non-NONE entries (firstn EMIT)."""
    W = w.shape[1]
    keys = jnp.where(w == ITEM_NONE, W, 0) + jnp.arange(W)[None, :]
    order = jnp.argsort(keys, axis=1)
    return jnp.take_along_axis(w, order, axis=1)


def _emit_blocks(blocks, result_max):
    """firstn EMIT of several take/emit blocks: each lane keeps its
    first ``result_max`` items that are not ITEM_NONE, in block then
    slot order, ITEM_NONE after them -> (N, result_max). ``blocks`` are
    the blocks' (N, numrep) results; their columns are laid side by
    side lane-dense, (cols, N) as the kernel writes them, and placed by
    a running count of the items before them and one select a column
    and output slot, where ``_compact``'s argsort of an (N, cols) array
    cost the rule VM 45 ms a 2^20-lane sweep on a v5e (PERF.md)."""
    cols = jnp.concatenate([b.T for b in blocks], axis=0)
    n = cols.shape[1]
    out = [jnp.full(n, ITEM_NONE, dtype=jnp.int32)] * result_max
    before = jnp.zeros(n, dtype=jnp.int32)
    for c in range(cols.shape[0]):
        item = cols[c]
        live = item != ITEM_NONE
        # column c can land at most in output slot c
        for j in range(min(c + 1, result_max)):
            out[j] = jnp.where(live & (before == j), item, out[j])
        before = before + live
    return jnp.stack(out, axis=1)


# ---------------------------------------------------------------------------
# Rule execution
# ---------------------------------------------------------------------------

# Narrowest block a Mapper chooses by itself. A block does full-width
# work whatever its tail mask, and every width is a program of its own,
# so the floor bounds the programs: six widths, 2^16..2^21, on the
# kernel path. Under 2^16 lanes the kernel takes less than 2.5 ms on a
# v5e (37 ns a lane) against the host's 6-7 ms a sweep (PERF.md):
# narrower would buy nothing.
MIN_BLOCK_WIDTH = 1 << 16

# Narrowest block that finishes its later rounds in narrower blocks
# (``_choose_indep_block``; the kernel's flagged-lane buffer in
# ``_choose_one_firstn``, which is this wide for a 2^21 block). Every
# narrow width is one more copy of the round body in the program, and
# under 2^13 lanes a full-width indep round takes a v5e under 3.5 ms
# (PERF.md): the copies would cost every small program its compile time
# to save less than a dispatch.
MIN_NARROW_WIDTH = 1 << 13


def narrow_widths(width: int) -> tuple[int, ...]:
    """Widths, widest first, of the blocks in which a block of
    ``width`` lanes finishes its later rounds: an eighth once no more
    lanes than that have a round to make, then a 128th; none for a
    block that never narrows."""
    return (width >> 3, width >> 7) if width >= MIN_NARROW_WIDTH else ()


def block_width(lanes: int, cap: int, floor: int = 1) -> int:
    """Width of the block that maps ``lanes`` lanes when the widest
    block is ``cap``: the next power of two at or above ``lanes``, at
    least ``floor``, never above ``cap`` (a cap below the floor wins).
    The one width rule of ``Mapper.sweep``, ``Mapper.map_pgs``'s tail
    block and ``sharded_sweep``'s per-shard tile."""
    if lanes >= cap:
        return cap
    return min(cap, max(floor, 1 << max(0, int(lanes) - 1).bit_length()))


class Mapper:
    """Compiled batched CRUSH mapper for one CrushMap.

    Usage:
        mapper = Mapper(crush_map)
        osds = mapper.map_pgs(ruleno, xs, numrep)   # (N, numrep) int32

    Each (ruleno, numrep, N-shape) triple compiles once; map mutations mean
    building a new Mapper (maps are cheap to pack — the arrays are the map).
    """

    def __init__(self, crush_map: CrushMap,
                 device_weights: np.ndarray | None = None,
                 block: int | None = None,
                 choose_args: int | None = None,
                 mesh=None, mesh_min_batch: int | None = None,
                 config: dict | None = None):
        _t0 = time.perf_counter()
        # LIVE config dict for the quarantine knobs
        # (crush_kernel_reprobe_*); None falls back to the process
        # devmon's config, which Cluster.install_faults points at the
        # cluster's shared dict — so a served cluster's knob flips
        # reach every Mapper without re-plumbing constructors.
        self._config = config
        self.map = crush_map
        self.packed: PackedMap = pack_map(crush_map)
        self.choose_args_key = choose_args
        # Legacy tunables (chooseleaf_stable=0 renames replica slots on
        # failure with data-dependent loop bounds; local retries change
        # the retry ladder shape): fall back to the scalar spec for the
        # whole map rather than refuse (round 1 raised here).
        self._scalar_reason = None
        if crush_map.tunables.chooseleaf_stable != 1:
            self._scalar_reason = "chooseleaf_stable=0"
        elif crush_map.tunables.choose_local_tries or \
                crush_map.tunables.choose_local_fallback_tries:
            self._scalar_reason = "legacy local retries"
        if self._scalar_reason:
            from ceph_tpu.utils.logging import get_logger
            get_logger("crush").dout(
                1, "vectorized mapper falling back to the scalar spec",
                reason=self._scalar_reason)
        p = self.packed
        if device_weights is None:
            device_weights = np.full(p.max_devices, WEIGHT_ONE,
                                     dtype=np.int64)
        with jax.enable_x64(True):
            # Staging discipline (round 6): each jnp.asarray is a
            # host->device transfer whose per-transfer LATENCY (not
            # bandwidth) dominated pack_seconds — the old
            # one-array-per-key staging paid ~17 round trips per
            # Mapper. The saving is not re-measured on a local chip;
            # the design stands. Now: the map-independent tables ride the
            # process-wide cache, the six (B, S) tables share ONE int64
            # shuttle (uint64 rides as bits, items as widened int32),
            # and the per-bucket scalar columns share one int32 array.
            rhlh_dev, ll_dev, zg2d_dev = _staged_const_tables()
            big64 = jnp.asarray(np.stack([
                p.items.astype(np.int64), p.weights, p.cumw,
                p.wm1.view(np.int64), p.wm0.view(np.int64),
                p.wsh.view(np.int64)]))
            meta32 = np.stack([
                p.size, p.alg, p.btype, p.bid,
                # one word per bucket: size | alg<<16 | btype<<20 — one
                # row gather per descent level instead of three
                (p.size.astype(np.int64)
                 | (p.alg.astype(np.int64) << 16)
                 | (p.btype.astype(np.int64) << 20)).astype(np.int32),
            ], axis=1)
            meta_dev = jnp.asarray(meta32, dtype=jnp.int32)  # (B, 5)
            devw_c = jnp.asarray(
                np.asarray(device_weights)[:, None], dtype=jnp.int64)
            _bits = jax.lax.bitcast_convert_type
            self.arrays = {
                "items": big64[0].astype(jnp.int32),
                "weights": big64[1],
                "cumw": big64[2],
                "wm1": _bits(big64[3], jnp.uint64),
                "wm0": _bits(big64[4], jnp.uint64),
                "wsh": _bits(big64[5], jnp.uint64),
                "size": meta_dev[:, 0],
                "alg": meta_dev[:, 1],
                "btype": meta_dev[:, 2],
                "bid": meta_dev[:, 3],
                "device_weights": devw_c[:, 0],
                # crush_ln's byte planes for the general draw's ladder
                "ln_rhlh": rhlh_dev,
                "ln_ll": ll_dev,
                # (B,1)/(D,1) copies: element gathers cost ~7ns/element
                # on this platform; row gathers are ~10x cheaper
                "size_c": meta_dev[:, 0:1],
                "alg_c": meta_dev[:, 1:2],
                "btype_c": meta_dev[:, 2:3],
                "meta_c": meta_dev[:, 4:5],
                "devw_c": devw_c,
                # ln-equality pair predicate as a (256,256) one-hot-
                # matmul table (see _zg_pair)
                "zg2d": zg2d_dev,
            }
            if p.tree_depth_max:
                self.arrays["tree_nodes"] = jnp.asarray(p.tree_nodes,
                                                        dtype=jnp.int64)
                self.arrays["tree_num"] = jnp.asarray(p.tree_num,
                                                      dtype=jnp.int32)
            if ALG_STRAW in p.algs_present:
                self.arrays["straws"] = jnp.asarray(p.straws,
                                                    dtype=jnp.uint64)
            if choose_args is not None and \
                    choose_args in crush_map.choose_args:
                from ceph_tpu.crush.tensors import pack_choose_args
                cw, cids, cm1, cm0, csh = pack_choose_args(
                    crush_map, choose_args, p)
                self.arrays["cw"] = jnp.asarray(cw, dtype=jnp.int64)
                self.arrays["cids"] = jnp.asarray(cids, dtype=jnp.int32)
                self.arrays["cm1"] = jnp.asarray(cm1, dtype=jnp.uint64)
                self.arrays["cm0"] = jnp.asarray(cm0, dtype=jnp.uint64)
                self.arrays["csh"] = jnp.asarray(csh, dtype=jnp.uint64)
        # Static fast-path flags (part of the jit key):
        # all_uniform — every straw2 bucket qualifies for the exact
        # uniform-weight draw (tensors.PackedMap.uniform) and no
        # choose_args weight-set is packed;
        # skip_is_out — every device weight is full, so is_out is
        # compile-time False (reweighting recompiles once, see
        # set_device_weights).
        straw2_rows = (p.alg == ALG_STRAW2) & (p.size > 0)
        self._all_uniform = bool(
            np.all(p.uniform[straw2_rows] == 1)) and             "cw" not in self.arrays
        self._skip_is_out = bool(
            np.all(np.asarray(device_weights) == WEIGHT_ONE))
        self.cfg = {"max_depth": p.max_depth,
                    "present": p.algs_present,
                    "type_depth": p.type_depth,
                    "tree_depth": p.tree_depth_max,
                    "all_uniform": self._all_uniform,
                    "skip_is_out": self._skip_is_out}
        # Fused Pallas kernel (round 4): the whole rule in one VMEM
        # program for eligible (straw2/uniform/firstn) maps — see
        # pallas_mapper. "auto" = on when the default backend is TPU;
        # "interpret" runs the kernel through the Pallas interpreter on
        # CPU (tests); "0" disables.
        mode = os.environ.get("CEPH_TPU_CRUSH_KERNEL", "auto")
        self._kernel_mode = None
        if not self._scalar_reason:
            if mode == "interpret":
                self._kernel_mode = "interpret"
            elif mode in ("1", "auto") and \
                    jax.default_backend() == "tpu":
                self._kernel_mode = "tpu"
        self._kernel_plans: dict[int, object] = {}
        self._kernel_bodies: dict[tuple, object] = {}
        self._kernel_fns: dict[tuple, object] = {}
        # Tile size bounding the (block, S) int64 straw2 temps: target
        # ~2 GiB of transient state assuming ~8 live (S-wide int64) temps
        # across numrep*SPEC_TRIES speculative lanes per PG.
        if block is None:
            budget = 2 << 30
            per_lane = max(1, p.max_size) * 8 * 8 * (3 * SPEC_TRIES)
            block = max(1 << 14, min(1 << 20, budget // per_lane))
            block = 1 << (block.bit_length() - 1)       # power of two
        self.block = block
        # Multi-chip (round 10): with a mesh attached, sweep/map_pgs
        # batches of at least mesh_min_batch lanes route through
        # crush.sharded_sweep (PG batch sharded over the mesh axis,
        # map tensors replicated, zero collectives on the hot path).
        self.mesh = mesh
        if mesh_min_batch is None:
            from ceph_tpu.crush.sharded_sweep import MESH_MIN_BATCH
            mesh_min_batch = MESH_MIN_BATCH
        self.mesh_min_batch = mesh_min_batch
        # Which engine the LAST map_pgs/sweep actually executed on
        # ('pallas'/'pallas-interpret'/'xla'/'scalar', '+sharded'
        # suffix on the mesh path) — bench rows diff this against
        # mapping_path()'s prediction so a silent mid-run kernel
        # degrade is a recorded fact, not a mystery slowdown.
        self.last_map_path: str | None = None
        # devmon identity (round 14): the incarnation token keys
        # per-Mapper jit wrappers' compile warmth; the arrays
        # signature (lazy — see _jit_key) keys shared lru'd programs
        # the way jax itself does (abstract input shapes), so a new
        # Mapper over a differently-shaped map counts its real
        # recompile instead of reading warm off the shared fn object.
        # Set AFTER a kernel failure: the engine this Mapper's plan
        # promised before it degraded — under
        # devmon_expected_engine=auto every later sweep keeps counting
        # a mismatch instead of the baseline silently re-healing to
        # the fallback engine (a kernel-path map served by the XLA
        # path with no signal; how much slower that is on a v5e is not
        # measured).
        self._devmon_token = next(_MAPPER_TOKEN)
        self._arrays_sig: tuple | None = None
        self._degraded_from: str | None = None
        # Kernel quarantine state machine (round 16): a kernel failure
        # no longer permanently drops to XLA — the kernel is
        # quarantined (XLA serves) and re-probed on capped exponential
        # backoff; only crush_kernel_reprobe_disable_after CONSECUTIVE
        # probe failures make it permanent. See _disable_kernel /
        # _maybe_reprobe.
        self._quar_state: str | None = None  # quarantined|reprobing|permanent
        self._quar_mode: str | None = None   # kernel mode to restore
        self._quar_failures = 0              # consecutive failures
        self._quar_next_probe = 0.0          # monotonic deadline
        PERF.inc("packs")
        PERF.tinc("pack_seconds", time.perf_counter() - _t0)
        # device-runtime accounting (round 14): the pack's H2D staging
        # footprint — what actually crossed the host boundary (the
        # int64 shuttle, the meta columns, device weights, optionals);
        # the process-cached const tables (ln planes, zg2d) ship once per
        # process and are excluded. big64 itself is the one transfer
        # its six views share.
        staged = int(big64.nbytes) + int(meta_dev.nbytes) + \
            int(devw_c.nbytes) + sum(
                int(self.arrays[k].nbytes) for k in
                ("tree_nodes", "tree_num", "straws", "cw", "cids",
                 "cm1", "cm0", "csh") if k in self.arrays)
        _devmon().record_h2d(staged)
        _devmon().note_staging(staged)

    def attach_mesh(self, mesh, mesh_min_batch: int | None = None):
        """Route big sweeps through the mesh-sharded path (round 10)."""
        self.mesh = mesh
        if mesh_min_batch is not None:
            self.mesh_min_batch = mesh_min_batch

    def _use_mesh(self, n: int) -> bool:
        return (self.mesh is not None and not self._scalar_reason
                and self.mesh.devices.size > 1
                and n >= self.mesh_min_batch)

    def set_device_weights(self, device_weights: np.ndarray) -> None:
        """Update reweights (is_out vector). No recompile unless the
        all-devices-full flag flips (then exactly one)."""
        PERF.inc("reweights")
        _was = self._skip_is_out
        with jax.enable_x64(True):
            devw_c = jnp.asarray(                 # one transfer, two views
                np.asarray(device_weights)[:, None], dtype=jnp.int64)
            self.arrays["device_weights"] = devw_c[:, 0]
            self.arrays["devw_c"] = devw_c
        self._skip_is_out = bool(
            np.all(np.asarray(device_weights) == WEIGHT_ONE))
        self.cfg["skip_is_out"] = self._skip_is_out
        self._arrays_sig = None          # devw shapes may have changed
        if self._skip_is_out != _was:
            PERF.inc("reweight_recompiles")
        # kernel plans embed the non-full-device list: rebuild lazily
        self._kernel_plans.clear()
        self._kernel_bodies.clear()
        self._kernel_fns.clear()
        # compiled shard programs close over the kernel bodies just
        # dropped — without this they pin the retired plans for the
        # Mapper's lifetime (crush/sharded_sweep._shard_fn)
        self.__dict__.pop("_sharded_fns", None)

    # -- fused Pallas kernel path (round 4) --------------------------------
    def _knob(self, name: str, default):
        """crush_kernel_reprobe_* knobs, read LIVE from this Mapper's
        config dict (or the process devmon's — see __init__)."""
        cfg = self._config if self._config is not None \
            else _devmon().config
        try:
            return type(default)(cfg.get(name, default))
        except (TypeError, ValueError):
            return default

    def _disable_kernel(self, where: str, exc: Exception) -> None:
        """Quarantine the fused kernel after a failure: XLA serves
        while a re-probe is pending on capped exponential backoff
        (crush_kernel_reprobe_base/_max); after
        crush_kernel_reprobe_disable_after CONSECUTIVE failures the
        quarantine is permanent (today's sticky behavior, for a
        genuinely broken libtpu).

        The fused kernel is an optimization, never a correctness
        dependency: any compile/runtime failure (e.g. a libtpu with a
        tighter scoped-VMEM limit than the build_plan model assumes)
        must degrade to the always-correct XLA path instead of killing
        the caller — round 4's driver bench died exactly this way."""
        from ceph_tpu.utils.logging import get_logger
        PERF.inc("kernel_exec_failures")
        # the engine this Mapper PROMISED before degrading: keeps the
        # expected-vs-actual baseline honest (see _devmon_token note)
        self._degraded_from = "pallas"
        if self._quar_mode is None:
            self._quar_mode = self._kernel_mode
        self._kernel_mode = None
        self._kernel_plans.clear()
        self._kernel_bodies.clear()
        self._kernel_fns.clear()
        self.__dict__.pop("_sharded_fns", None)   # see set_device_weights
        entering = self._quar_state is None
        self._quar_failures += 1
        disable_after = max(
            1, self._knob("crush_kernel_reprobe_disable_after", 5))
        dm = _devmon()
        if self._quar_failures >= disable_after:
            self._quar_state = "permanent"
            self._quar_next_probe = float("inf")
            get_logger("crush").dout(
                0, f"fused CRUSH kernel failed in {where} "
                   f"({type(exc).__name__}: {str(exc)[:200]}) — "
                   f"{self._quar_failures} consecutive failures, "
                   f"permanently disabled for this Mapper")
        else:
            base = self._knob("crush_kernel_reprobe_base", 0.5)
            cap = self._knob("crush_kernel_reprobe_max", 30.0)
            backoff = min(base * (2 ** (self._quar_failures - 1)), cap)
            self._quar_next_probe = time.monotonic() + backoff
            self._quar_state = "quarantined" if entering else "reprobing"
            get_logger("crush").dout(
                0, f"fused CRUSH kernel failed in {where} "
                   f"({type(exc).__name__}: {str(exc)[:200]}) — "
                   f"quarantined (XLA serves; re-probe in "
                   f"{backoff:.2f}s, failure "
                   f"{self._quar_failures}/{disable_after})")
        if entering:
            dm.record_quarantine_enter(self._devmon_token,
                                       self._quar_state)
        else:
            dm.set_quarantine_state(self._devmon_token,
                                    self._quar_state)

    def _maybe_reprobe(self, ruleno: int, result_max: int) -> None:
        """Run one backoff-paced quarantine probe when due (called at
        the top of fresh map_pgs/sweep entries — never from the
        degrade-retry re-entry, so a probe can't recurse into the
        failure that scheduled it)."""
        if self._quar_state in (None, "permanent"):
            return
        if time.monotonic() < self._quar_next_probe:
            return
        self._reprobe(ruleno, result_max)

    def _reprobe(self, ruleno: int, result_max: int) -> None:
        """One probe: rebuild the kernel body, run it on a small PG
        sample, compare BIT-EXACT against the serving XLA path. Pass
        -> re-promote (quarantine exits, failure count resets); raise
        or mismatch -> back to quarantine with doubled backoff."""
        from ceph_tpu.utils.logging import get_logger
        dm = _devmon()
        self._kernel_mode = self._quar_mode
        self._kernel_plans.clear()
        self._kernel_bodies.clear()
        self._kernel_fns.clear()
        self.__dict__.pop("_sharded_fns", None)
        try:
            kb = self._kernel_body(ruleno, result_max)
        except Exception as e:
            dm.record_probe(False)
            PERF.inc("kernel_probes")
            self._disable_kernel("reprobe", e)
            return
        if kb is None:
            # this (rule, width) never rides the kernel — nothing to
            # judge here; stand down and probe on a kernel-eligible
            # call instead
            self._kernel_mode = None
            self._kernel_bodies.clear()
            return
        PERF.inc("kernel_probes")
        nprobe = 128
        try:
            with jax.enable_x64(True):
                xs = jnp.arange(nprobe, dtype=jnp.uint32)
                fn = jax.jit(kb)
                got = np.asarray(dm.jit_call(
                    "crush_map_pgs",
                    self._jit_key(ruleno, result_max, True,
                                  ("probe", nprobe)),
                    fn, self.arrays, xs))
                ref = np.asarray(dm.jit_call(
                    "crush_map_pgs",
                    self._jit_key(ruleno, result_max, False, nprobe),
                    self._rule_fn(ruleno, result_max),
                    self.arrays, xs))
            if not np.array_equal(got, ref):
                bad = int((got != ref).sum())
                raise RuntimeError(
                    f"probe mismatch: kernel disagrees with the "
                    f"serving path on {bad}/{got.size} slots")
        except Exception as e:
            dm.record_probe(False)
            self._disable_kernel("reprobe", e)
            return
        # bit-exact: re-promote
        dm.record_probe(True)
        self._kernel_fns[(ruleno, result_max)] = fn
        PERF.inc("kernel_compiles")
        PERF.inc("kernel_repromotes")
        self._quar_state = None
        self._quar_mode = None
        self._quar_failures = 0
        self._quar_next_probe = 0.0
        self._degraded_from = None
        dm.record_quarantine_exit(self._devmon_token)
        get_logger("crush").dout(
            0, f"fused CRUSH kernel re-promoted after quarantine "
               f"(probe bit-exact vs the serving path on {nprobe} "
               f"PGs, rule {ruleno})")

    def kernel_quarantine_info(self) -> dict | None:
        """The quarantine state machine's live view (bench / status),
        or None when the kernel is healthy."""
        if self._quar_state is None:
            return None
        due = self._quar_next_probe - time.monotonic()
        return {"state": self._quar_state,
                "failures": self._quar_failures,
                "next_probe_in_s": (round(max(due, 0.0), 3)
                                    if self._quar_state != "permanent"
                                    else None)}

    def _kernel_plan(self, ruleno: int):
        if ruleno not in self._kernel_plans:
            self._kernel_plans[ruleno] = _pm.build_plan(
                self.map, self.packed, ruleno,
                np.asarray(self.arrays["device_weights"]),
                self.choose_args_key)
            PERF.inc("kernel_plans")
        return self._kernel_plans[ruleno]

    @staticmethod
    def _plan_numrep(plan, result_max: int) -> int:
        """The replica count the kernel is built for: the rule's arg1
        (<= 0 means fill from result_max, like the rule VM), clamped
        to the requested width. Shared by _kernel_body and
        kernel_plan_info so the reported geometry always describes
        the kernel actually built."""
        numrep = plan.numrep_arg if plan.numrep_arg > 0 \
            else plan.numrep_arg + result_max
        return min(numrep, result_max)

    def _take_plans(self, ruleno: int) -> tuple:
        """The kernel plans of the rule's take/emit blocks in the rule's
        order (one for a rule of one block), or () where no plan
        serves."""
        plan = self._kernel_plan(ruleno)
        if plan is None:
            return ()
        return plan if isinstance(plan, tuple) else (plan,)

    def _kernel_body(self, ruleno: int, result_max: int,
                     tally: bool = False):
        """fn_body(arrs, xs) -> (N, result_max), backed by the fused
        kernel with a masked XLA fallback for flagged lanes, or None
        when this rule is ineligible (the XLA path stands). A rule of
        several take/emit blocks runs one kernel plan a block, each
        with its own fallback, in the one body, and keeps a lane's
        first ``result_max`` items in block then slot order, as firstn
        EMIT does (``_emit_blocks``). With ``tally`` the body returns
        ``(mappings, stats)``, stats the block's ``KERNEL_TALLY``
        summed over its take plans."""
        if self._kernel_mode is None:
            return None
        key = (ruleno, result_max, tally)
        if key in self._kernel_bodies:
            return self._kernel_bodies[key]
        plans = self._take_plans(ruleno)
        numreps = [self._plan_numrep(p, result_max) for p in plans]
        body = None
        if plans and min(numreps) >= 1:
            body = self._make_kernel_body(plans, ruleno, result_max,
                                          numreps, tally)
        self._kernel_bodies[key] = body
        return body

    def _make_kernel_body(self, plans, ruleno: int, result_max: int,
                          numreps, tally: bool):
        roots = [s.arg1 for s in self.map.rules[ruleno].steps
                 if s.op == OP_TAKE]
        blocks = [self._take_block(plan, root, numrep)
                  for plan, root, numrep in zip(plans, roots, numreps)]

        def fn_body(arrs, xs):
            outs = [run(arrs, xs) for run in blocks]
            if len(outs) > 1:
                w = _emit_blocks([o[0] for o in outs], result_max)
            else:       # a rule of one block: its program as it was
                w = outs[0][0]
                if w.shape[1] < result_max:
                    padc = jnp.full((w.shape[0], result_max - w.shape[1]),
                                    ITEM_NONE, dtype=jnp.int32)
                    w = jnp.concatenate([w, padc], axis=1)
                w = w[:, :result_max]
            if not tally:
                return w
            # KERNEL_TALLY, from the flags alone: whether a block's
            # recompute ran, and for more than one pass, is a function
            # of their count
            stats = None
            for _, bad, FB in outs:
                flagged = jnp.sum(bad, dtype=jnp.int32)
                s = jnp.stack([flagged, (flagged > 0).astype(jnp.int32),
                               (flagged > FB).astype(jnp.int32)])
                stats = s if stats is None else stats + s
            return w, stats

        return fn_body

    def _take_block(self, plan, root: int, numrep: int):
        """run(arrs, xs) -> (w, bad, FB) of one take/emit block on the
        kernel: ``w`` its (N, numrep) items with the lanes the kernel
        flagged (``bad``) recomputed bit-exactly on the XLA path from
        ``root``, FB lanes a pass."""
        interpret = self._kernel_mode == "interpret"
        root_type = self.map.buckets[root].type
        t = self.map.tunables
        tries = t.choose_total_tries
        recurse_tries = 1 if t.chooseleaf_descend_once else tries
        cfg = dict(self.cfg)
        cfg["levels_main"] = _depth_between(
            self.cfg["type_depth"], root_type, plan.target_type)
        cfg["levels_leaf"] = (_depth_between(
            self.cfg["type_depth"], plan.target_type, 0)
            if plan.recurse else None)
        root_row = -1 - root
        # pad to the candidate-batched PG cell width (round 15): the
        # candidate axis folds into the lane axis, so the per-cell PG
        # width is plan.lanes // fold, not plan.lanes
        lanes = _pm.kernel_geometry(plan, numrep + _pm.SPEC_EXTRA)[0]

        def run(arrs, xs):
            n = xs.shape[0]
            pad = -n % lanes
            xs_k = jnp.pad(xs, (0, pad)) if pad else xs
            leaves, bad = _pm._run_kernel(
                plan, xs_k.astype(jnp.int32), numrep,
                interpret=interpret)
            leaves, bad = leaves[:n], bad[:n]

            # XLA fallback for flagged lanes: the loop path recomputes
            # them bit-exactly. Two causes. Candidate-table exhaustion:
            # a lane whose numrep + SPEC_EXTRA candidates hold fewer
            # than numrep different items of the failure domain; ~1e-8
            # a lane over 640 hosts, but 1.8e-3 for 3 replicas over 20
            # racks (5 draws landing on 2 racks or fewer). Ambiguous
            # class or continuous draws: ~1e-6 to ~2e-4 a lane by
            # bucket weight scale -- heavy buckets draw small quotients
            # where genuine floor ties concentrate. Counted on a v5e
            # (``kernel_flagged_lanes``, PERF.md, PR 35): 2,009 a
            # million on the 10,240-OSD map with a compat weight-set,
            # 4,220 lanes of a 2^21 block. So flags land EVERY block
            # and the fallback must not cost O(block): gather the
            # flagged lanes into a small buffer (``fallback_lanes``: a
            # 256th of the block, 1.94 times that rate), recompute only
            # those, scatter back. Fill slots recompute lanes that were
            # not flagged and scatter their (identical, because
            # recomputation is exact) values — no masking needed. A
            # block with more flags than the buffer holds takes as many
            # passes of it as it needs (counted:
            # ``kernel_fallback_overflows``), not a recompute of the
            # whole block: at 2^21 lanes that held half of the sweep
            # step's ln ladders and nearly all of its 4 GB of
            # temporaries.
            FB = fallback_lanes(n)
            # Where the plan draws inside a margin the recompute draws
            # on the general path (2 ms a round of 8,192 lanes on a v5e,
            # 11.2 while its ln was a table gather), and at one width a
            # slot's loop goes round for its unluckiest lane (a flagged
            # lane's third replica collides on its first three tries by
            # what flagged it, then one time in ten): 14.5 rounds a
            # block, and a sweep's time follows its ids (six seeded runs
            # spread by 0.66%; PERF.md §6). So the buffer's later rounds
            # run in narrower blocks (``_choose_one_firstn``), 7 of them
            # at full width. An all-uniform plan's recompute draws by
            # hash alone (14 ms of a window's 3.5 s) at one width.
            narrow = narrow_widths(FB) if plan.rhlh is not None else ()

            def _recompute(xs_):
                nn = xs_.shape[0]
                rows = jnp.full(nn, root_row, dtype=jnp.int32)
                active = jnp.ones(nn, dtype=bool)
                fb = jnp.full((nn, numrep), ITEM_NONE, dtype=jnp.int32)
                fb_lv = jnp.full((nn, numrep), ITEM_NONE,
                                 dtype=jnp.int32)
                for rep in range(numrep):
                    item, leaf, ok = _choose_one_firstn(
                        arrs, cfg, rows, active, xs_, rep,
                        fb[:, :rep], fb_lv[:, :rep], plan.target_type,
                        plan.recurse, tries, recurse_tries,
                        plan.vary_r, narrow=narrow)
                    fb = fb.at[:, rep].set(
                        jnp.where(ok, item, ITEM_NONE))
                    fb_lv = fb_lv.at[:, rep].set(
                        jnp.where(ok, leaf, ITEM_NONE))
                return _compact(fb_lv if plan.recurse else fb)

            def _pass(c):
                # top_k, not jnp.nonzero: nonzero's lowering inside a
                # lax.cond crashed this platform's TPU compile helper
                # outright. top_k is stable, so the FB indices are the
                # flagged lanes left first, then fill lanes — whose
                # recomputed (identical) values scatter harmlessly.
                left, w = c
                _, idx = jax.lax.top_k(left.astype(jnp.int32), FB)
                return (left.at[idx].set(False),
                        w.at[idx].set(_recompute(xs[idx])))

            # one pass on all but a rare block, none without a flag
            _, w = jax.lax.while_loop(lambda c: jnp.any(c[0]), _pass,
                                      (bad.astype(bool), leaves))
            return w, bad, FB

        return run

    def _rule_key(self, ruleno: int, result_max: int):
        rule = self.map.rules[ruleno]
        # TAKE steps carry the taken bucket's (static) type so the rule VM
        # can unroll exact descent depths on uniform hierarchies.
        steps = []
        for s in rule.steps:
            if s.op == OP_TAKE and s.arg1 < 0 and s.arg1 in self.map.buckets:
                steps.append((s.op, s.arg1, s.arg2,
                              self.map.buckets[s.arg1].type))
            else:
                steps.append((s.op, s.arg1, s.arg2))
        return (tuple(steps), result_max, _tunables_key(self.map.tunables),
                self.cfg["max_depth"], self.cfg["present"],
                self.cfg["type_depth"], self.cfg["tree_depth"],
                (self._all_uniform, self._skip_is_out))

    def _rule_fn(self, ruleno: int, result_max: int):
        return _compiled_rule(*self._rule_key(ruleno, result_max))

    def mapping_path(self, ruleno: int, result_max: int) -> str:
        """Which engine serves this (rule, width): 'pallas' (fused
        kernel on TPU), 'pallas-interpret' (tests), 'xla' (vectorized
        general path), or 'scalar' (legacy-tunable spec walk). A rule
        of several take/emit blocks is 'pallas' when ``build_plan``
        takes every block (a plan a block, merged as EMIT keeps the
        first ``result_max``) and 'xla' when it refuses one. Bench
        rows record this so a variant silently sliding off the kernel
        is a visible diff, not a mystery slowdown."""
        if self._scalar_reason:
            return "scalar"
        if self._kernel_body(ruleno, result_max) is not None:
            return ("pallas-interpret"
                    if self._kernel_mode == "interpret" else "pallas")
        return "xla"

    def kernel_plan_info(self, ruleno: int, result_max: int
                         ) -> dict | None:
        """Structural facts of the fused-kernel plan serving
        (rule, width), or None when the XLA/scalar path stands.
        Bench rows attach this verbatim (crush_sweep.sweep_rate):

        - ``fetches_per_sweep``: fused level fetch+choose passes per
          grid cell — groups * l_total since the round-15 candidate
          batching; a PER-CELL count, only comparable across rounds
          together with ``kernel_lanes`` (the cell's PG width, which
          the geometry may change): the honest per-PG comparison is
          ``fetch_amortization`` below. The level-0 entry is the
          hoisted shared-root broadcast, not a matmul;
        - ``fetch_amortization``: per-PG level-pass reduction vs the
          candidate-major baseline at this plan's own width —
          (n_cand/plan.lanes) / (groups/kernel_lanes); 1.0 means the
          geometry degenerated to the old kernel (no VMEM headroom),
          n_cand is the ideal full fold at unchanged cell width;
        - ``candidate_batched``: more than one candidate rides each
          level pass (fold > 1);
        - ``kernel_lanes`` / ``candidate_fold``: the per-cell PG
          width and fold the geometry search chose for this map.

        A rule of several take/emit blocks gives each fact as a list,
        one entry a block's plan, and ``take_plans``, their count.
        """
        if self._scalar_reason or \
                self._kernel_body(ruleno, result_max) is None:
            return None
        infos = []
        for plan in self._take_plans(ruleno):
            n_cand = self._plan_numrep(plan, result_max) + _pm.SPEC_EXTRA
            lanes, fold, groups = _pm.kernel_geometry(plan, n_cand)
            infos.append({
                "fetches_per_sweep": groups * (plan.l_main + plan.l_leaf),
                "fetch_amortization": round(
                    n_cand * lanes / (groups * plan.lanes), 3),
                "candidate_batched": fold > 1,
                "kernel_lanes": lanes,
                "candidate_fold": fold,
            })
        if len(infos) == 1:
            return infos[0]
        return {"take_plans": len(infos),
                **{k: [i[k] for i in infos] for k in infos[0]}}

    def expected_path(self, ruleno: int, result_max: int) -> str:
        """The engine this Mapper is EXPECTED to serve (rule, width)
        on: the built plan's prediction — EXCEPT a Mapper whose fused
        kernel failed mid-run stays pinned to the engine it promised
        ('pallas'), so under ``devmon_expected_engine=auto`` a
        permanently lost plan keeps counting as a mismatch on every
        sweep instead of silently re-healing the baseline to the
        fallback engine."""
        return self._degraded_from or \
            self.mapping_path(ruleno, result_max)

    def _jit_key(self, ruleno: int, result_max: int, kernel: bool,
                 extra) -> tuple:
        """The devmon compile-warmth key, mirroring the REAL jit cache
        identity: per-Mapper kernel wrappers are cold once per Mapper
        incarnation (the token — id(fn) is GC-recyclable); shared
        lru'd XLA programs are warm exactly when jax's own cache is —
        same rule key AND same abstract input shapes (the staged
        arrays' signature; a new Mapper over a differently-shaped map
        genuinely recompiles). Kernel keys carry the kernel-variant
        tag (round 15): a `jit_compile` span must distinguish a
        fresh batched-kernel compile from a stale plan's re-trace —
        the tag bumps whenever the kernel body restructures."""
        if kernel:
            return ("kern", _pm.KERNEL_VARIANT, self._devmon_token,
                    ruleno, result_max, extra)
        if self._arrays_sig is None:
            self._arrays_sig = tuple(sorted(
                (k, tuple(v.shape)) for k, v in self.arrays.items()))
        return ("xla", self._rule_key(ruleno, result_max),
                self._arrays_sig, extra)

    def rule_is_firstn(self, ruleno: int) -> bool:
        """True when the rule's choose steps are firstn (replicated)."""
        return not any(s.op in (OP_CHOOSE_INDEP, OP_CHOOSELEAF_INDEP)
                       for s in self.map.rules[ruleno].steps)

    def takes(self, ruleno: int) -> int:
        """The rule's take/emit blocks: its TAKE steps (``build_plan``
        gives a rule of several one kernel plan a block)."""
        return sum(s.op == OP_TAKE for s in self.map.rules[ruleno].steps)

    def _scalar_map(self, ruleno: int, xs, result_max: int) -> np.ndarray:
        """Legacy-tunable fallback: per-x scalar walk of the executable
        spec (bit-exact by definition; slow — legacy maps only)."""
        from ceph_tpu.crush import mapper_ref
        weight = np.asarray(self.arrays["device_weights"]).tolist()
        cargs = self.map.choose_args.get(self.choose_args_key) \
            if self.choose_args_key is not None else None
        out = np.full((len(xs), result_max), ITEM_NONE, dtype=np.int32)
        for i, x in enumerate(np.asarray(xs)):
            got = mapper_ref.do_rule(self.map, ruleno, int(x), result_max,
                                     weight, cargs)
            out[i, :len(got[:result_max])] = got[:result_max]
        return out

    def effective_block(self, ruleno: int, result_max: int) -> int:
        """The widest block sweep/map_pgs use for this rule (kernel-path
        rules take wider blocks; a block with fewer lanes left to map
        is narrower, see ``block_width``) — the sharded path tiles on
        it and benches quantize their two-size slope on it, not on
        self.block."""
        if self._scalar_reason:
            return self.block
        return self._block_cap(
            self._kernel_body(ruleno, result_max) is not None)

    def _block_cap(self, kernel: bool) -> int:
        """The widest block. The fused kernel's working set is
        VMEM-resident per LANES-wide grid cell (no (N, S) straw2 temps),
        so it takes much wider blocks than the XLA path's memory bound:
        fewer dispatches for a long sweep."""
        return max(self.block, 1 << 21) if kernel else self.block

    def _block_for(self, kernel: bool, lanes: int) -> int:
        """Width of the block that maps the next ``lanes`` lanes."""
        return block_width(lanes, self._block_cap(kernel), MIN_BLOCK_WIDTH)

    def _record_path(self, path: str, expected: str | None) -> str:
        """Per-CALL path record (round 14): the returned value is this
        call's own engine — immune to the interleaving that makes the
        single-slot ``last_map_path`` attribute (kept as a best-effort
        mirror for existing readers) unreliable when two sweeps from
        two PGs overlap. Also feeds the process devmon: a launch
        counter by engine, and an expected-vs-actual check so a plan
        that degraded DURING this call is a counted mismatch, not a
        mystery slowdown."""
        self.last_map_path = path            # best-effort mirror only
        dm = _devmon()
        dm.record_launch(path)
        if expected is not None:
            dm.record_path_check(expected, path)
        return path

    def map_pgs(self, ruleno: int, xs, result_max: int) -> jax.Array:
        """Vectorized crush_do_rule over xs -> (N, result_max) device ids
        (ITEM_NONE fills failures/indep holes). Tiled into block-lane
        chunks so straw2 temps stay bounded at any N. The engine path
        is recorded per call — ``map_pgs_path`` returns it."""
        out, _path = self.map_pgs_path(ruleno, xs, result_max)
        return out

    def map_pgs_path(self, ruleno: int, xs, result_max: int,
                     _expected: str | None = None
                     ) -> tuple[jax.Array, str]:
        """``map_pgs`` returning ``(out, path)`` — ``path`` is the
        engine THIS call executed on. ``_expected`` is internal: the
        engine predicted at first entry, threaded through the
        kernel-failure retry so a mid-call degrade records exactly one
        mismatch against the original plan."""
        if self._scalar_reason:
            PERF.inc("pgs_mapped", len(xs))
            return (self._scalar_map(ruleno, xs, result_max),
                    self._record_path("scalar", _expected))
        if _expected is None:
            self._maybe_reprobe(ruleno, result_max)
            _expected = self.expected_path(ruleno, result_max)
        if self._use_mesh(len(xs)):
            out = self._sharded_map_pgs(ruleno, xs, result_max)
            path = self.mapping_path(ruleno, result_max) + "+sharded"
            return out, self._record_path(path, _expected)
        kb = self._kernel_body(ruleno, result_max)
        if kb is not None:
            key = (ruleno, result_max)
            fn = self._kernel_fns.get(key)
            if fn is None:
                fn = jax.jit(kb)
                self._kernel_fns[key] = fn
                PERF.inc("kernel_compiles")
        else:
            fn = self._rule_fn(ruleno, result_max)
        kb_kern = kb is not None
        block = self._block_cap(kb_kern)
        if len(xs) == 0:     # the kernel rejects n=0 (and the guard
            with jax.enable_x64(True):     # readback would IndexError)
                return (jnp.zeros((0, result_max), dtype=jnp.int32),
                        _expected)
        dm = _devmon()
        try:
            with jax.enable_x64(True):
                xs = jnp.asarray(xs, dtype=jnp.uint32)
                n = xs.shape[0]
                if n <= block:
                    out = dm.jit_call(
                        "crush_map_pgs",
                        self._jit_key(ruleno, result_max, kb_kern, n),
                        fn, self.arrays, xs)
                else:
                    pieces = []
                    for start in range(0, n, block):
                        piece = xs[start:start + block]
                        lanes = piece.shape[0]
                        # the tail block is padded to the width its
                        # lanes need, so the jit cache holds one entry
                        # a width (block_width), not one a length
                        width = self._block_for(kb_kern, lanes)
                        if lanes < width:
                            piece = jnp.pad(piece, (0, width - lanes))
                        pieces.append(dm.jit_call(
                            "crush_map_pgs",
                            self._jit_key(ruleno, result_max, kb_kern,
                                          width),
                            fn, self.arrays, piece)[:lanes])
                    out = jnp.concatenate(pieces, axis=0)
                if kb is not None:
                    # dispatch is async: an execution-time kernel
                    # failure would otherwise surface at the CALLER's
                    # materialization, past this except. A one-element
                    # readback (not block_until_ready — on this
                    # platform that returns pre-execution) forces it
                    # here where the fallback can catch it.
                    np.asarray(out[0])
        except Exception as e:
            if kb is None:
                raise                        # XLA path: a real error
            self._disable_kernel("map_pgs", e)
            return self.map_pgs_path(ruleno, xs, result_max,
                                     _expected=_expected)
        path = self.mapping_path(ruleno, result_max)
        PERF.inc("pgs_mapped", int(n))       # success only: the failed
        if kb_kern:                          # attempt must not double-count
            self._count_take_plans(ruleno, -(-int(n) // block))
        return out, self._record_path(path, _expected)

    def _sharded_map_pgs(self, ruleno: int, xs, result_max: int):
        """map_pgs over the attached mesh (crush.sharded_sweep), with
        the same kernel-failure degrade discipline as the local path."""
        from ceph_tpu.crush import sharded_sweep as _ss
        kb = self._kernel_body(ruleno, result_max)
        try:
            out = _ss.sharded_map_pgs(self.mesh, self, ruleno, xs,
                                      result_max)
            if kb is not None and out.shape[0]:
                with jax.enable_x64(True):      # x64: the getitem traces
                    np.asarray(out[0])       # force execution: a run-
                # time kernel failure must surface inside this try
        except Exception as e:
            if kb is None:
                raise                        # XLA path: a real error
            self._disable_kernel("sharded_map_pgs", e)
            return self._sharded_map_pgs(ruleno, xs, result_max)
        # (last_map_path is set by sharded_map_pgs itself — one site)
        PERF.inc("pgs_mapped", len(xs))
        if kb is not None and len(xs):
            self._count_sharded_take_plans(ruleno, result_max, len(xs))
        return out

    def sweep(self, ruleno: int, start_x: int, n: int, result_max: int,
              device_counts_size: int | None = None):
        """Map [start_x, start_x + n) and aggregate ON DEVICE.

        One dispatch: a fori_loop over fixed-size blocks; per block the
        rule runs and ``_count_placements`` accumulates per-device
        placement counts; bad mappings (firstn rules only: fewer than
        result_max live devices — indep holes are expected output, ref:
        CrushTester's size check) are counted on device too.

        x is crush_do_rule's 32-bit input, so the range wraps modulo
        2^32 (a ``start_x`` at or past 2^32 is its low word).

        Returns (counts, bad): counts int64 (max_devices,), bad int64
        scalar; device arrays, or host arrays where the sweep carries a
        tally (an indep rule on the rule VM, a kernel plan with a
        margin draw), which comes back with them in one read. Nothing
        of O(n) touches the host. The engine path is recorded per call
        — ``sweep_path`` returns it."""
        counts, bad, _path = self.sweep_path(ruleno, start_x, n,
                                             result_max,
                                             device_counts_size)
        return counts, bad

    def sweep_path(self, ruleno: int, start_x: int, n: int,
                   result_max: int,
                   device_counts_size: int | None = None,
                   _expected: str | None = None):
        """``sweep`` returning ``(counts, bad, path)`` — ``path`` is
        the engine THIS sweep executed on (see map_pgs_path for the
        per-call discipline and the ``_expected`` retry threading).

        The call is the section ``crush.sweep`` (tags ``lanes``,
        ``takes``: the rule's take/emit blocks, ``blocks``, ``width``,
        an indep rule's ``narrow_width``): its
        self time is the prelude and the counters; each block's
        ``jit_call`` is a ``crush.dispatch``, the forced first-block read
        a ``crush.force``, the tally's read a ``crush.readback``. A
        kernel-failure retry nests inside the failed call's section."""
        with tracing.section("crush.sweep", service="crush") as sec:
            if sec:
                sec.tag("lanes", int(n)).tag("takes", self.takes(ruleno))
            return self._sweep_path(sec, ruleno, start_x, n, result_max,
                                    device_counts_size, _expected)

    def _sweep_path(self, sec, ruleno: int, start_x: int, n: int,
                    result_max: int, device_counts_size: int | None,
                    _expected: str | None):
        nd_ = device_counts_size or self.packed.max_devices
        if self._scalar_reason:    # legacy fallback: host aggregation
            PERF.inc("pgs_mapped", int(n))
            out = self._scalar_map(
                ruleno, (start_x % (1 << 32) + np.arange(n, dtype=np.uint64)
                         ).astype(np.uint32), result_max)
            live = out != ITEM_NONE
            counts = np.bincount(out[live], minlength=nd_)[:nd_]
            bad = int((live.sum(axis=1) < result_max).sum())
            return (np.asarray(counts, dtype=np.int64), np.int64(bad),
                    self._record_path("scalar", _expected))
        if _expected is None:
            self._maybe_reprobe(ruleno, result_max)
            _expected = self.expected_path(ruleno, result_max)
        if self._use_mesh(n) and device_counts_size is None:
            counts, bad = self._sharded_sweep(ruleno, start_x, n,
                                              result_max)
            path = self.mapping_path(ruleno, result_max) + "+sharded"
            return counts, bad, self._record_path(path, _expected)
        kb = self._kernel_body(ruleno, result_max)
        firstn = self.rule_is_firstn(ruleno)
        # a rule on the rule VM tallies what its choose blocks did (an
        # indep rule its rounds, a firstn rule its slots' loops), a
        # kernel plan with a margin draw what its fallback did
        indep = kb is None and not firstn
        tally = () if kb is not None else \
            INDEP_TALLY if indep else FIRSTN_TALLY
        fn_body = kb or _rule_body(*self._rule_key(ruleno, result_max),
                                   tally=tally)
        # a plan that decides draws inside a margin (a class or a
        # continuous level: it carries the crush_ln planes) sweeps with
        # the tally; an all-uniform plan flags candidate exhaustion
        # only, and its sweep program stays the one it was
        if kb is not None and any(p.rhlh is not None
                                  for p in self._take_plans(ruleno)):
            tally = KERNEL_TALLY
            fn_body = self._kernel_body(ruleno, result_max, tally=True)
        nd = device_counts_size or self.packed.max_devices
        kb_kern = kb is not None
        dm = _devmon()
        nblocks = lanes = width = 0
        forced = None
        try:
            with jax.enable_x64(True):
                counts = jnp.zeros(nd + 1, dtype=jnp.int64)
                bad = jnp.zeros(1 + len(tally), dtype=jnp.int64) \
                    if tally else jnp.int64(0)
                while lanes < n:
                    # every block is as wide as the lanes left need:
                    # a sweep under the cap is one block of its own
                    # width, a longer one ends in a narrower tail block
                    block = self._block_for(kb_kern, n - lanes)
                    step_fn = _compiled_sweep(fn_body, tally, nd, block,
                                              result_max)
                    key = self._jit_key(ruleno, result_max, kb_kern,
                                        (block, nd, firstn))
                    x0 = jnp.uint32((start_x + lanes) % (1 << 32))
                    left = jnp.int64(n - lanes)
                    with tracing.section("crush.dispatch",
                                         service="crush") as d:
                        if d:
                            d.tag("block", nblocks)
                        counts, bad = dm.jit_call(
                            "crush_sweep", key, step_fn, self.arrays,
                            counts, bad, x0, left)
                    nblocks += 1
                    lanes += block
                    width = max(width, block)
                    if kb_kern and block != forced:
                        # force the execution of the first block of
                        # each width (tiny readback; see map_pgs): a
                        # kernel that fails at run time must fail
                        # INSIDE this try. Blocks of one width run the
                        # identical program, so only the first can
                        # reveal a compile/launch fault, and the rest
                        # still pipeline (a narrower program runs last,
                        # where the caller's read-back follows anyway).
                        with tracing.section("crush.force",
                                             service="crush"):
                            np.asarray(counts[0])
                        forced = block
        except Exception as e:
            if kb is None:
                raise                        # XLA path: a real error
            self._disable_kernel("sweep", e)
            return self.sweep_path(ruleno, start_x, n, result_max,
                                   device_counts_size,
                                   _expected=_expected)
        if tally:
            # the tally comes back with the counts' read-back: one read
            with tracing.section("crush.readback", service="crush"):
                counts, bad = jax.device_get((counts, bad))
            for name, v in zip(tally, bad[1:]):
                PERF.inc(name, int(v))
            bad = bad[0]
        if sec:
            sec.tag("blocks", nblocks).tag("width", width)
            if indep:
                sec.tag("narrow_width",
                        next(iter(narrow_widths(width)), 0))
        path = self.mapping_path(ruleno, result_max)
        PERF.inc("pgs_mapped", int(n))       # success only (no double
        PERF.inc("sweep_blocks", nblocks)    # count via the retry)
        PERF.inc("sweep_lanes", lanes)
        if kb_kern:
            self._count_take_plans(ruleno, nblocks)
        return counts[:nd], bad, self._record_path(path, _expected)

    def _sharded_sweep(self, ruleno: int, start_x: int, n: int,
                       result_max: int):
        """Aggregated sweep over the attached mesh, with the same
        kernel-failure degrade discipline as the local path."""
        from ceph_tpu.crush import sharded_sweep as _ss
        kb = self._kernel_body(ruleno, result_max)
        try:
            counts, bad = _ss.sharded_sweep(self.mesh, self, ruleno,
                                            start_x, n, result_max)
            if kb is not None:
                with jax.enable_x64(True), \
                        tracing.section("crush.force", service="crush"):
                    # x64: counts is int64 and the getitem traces;
                    np.asarray(counts[0])    # force execution (see sweep)
        except Exception as e:
            if kb is None:
                raise                        # XLA path: a real error
            self._disable_kernel("sharded_sweep", e)
            return self._sharded_sweep(ruleno, start_x, n, result_max)
        # (last_map_path is set by sharded_sweep itself — one site)
        PERF.inc("pgs_mapped", int(n))
        if kb is not None:
            self._count_sharded_take_plans(ruleno, result_max, max(1, n))
        return counts, bad

    def _count_take_plans(self, ruleno: int, blocks: int) -> None:
        """``kernel_take_plans``: ``blocks`` blocks of lanes ran the
        kernel body, one plan a take/emit block of the rule each."""
        PERF.inc("kernel_take_plans", blocks * len(self._take_plans(ruleno)))

    def _count_sharded_take_plans(self, ruleno: int, result_max: int,
                                  n: int) -> None:
        """The same for a batch of ``n`` lanes over the mesh: every
        shard's tiles (``sharded_sweep._shard_widths``)."""
        from ceph_tpu.crush.sharded_sweep import _shard_widths
        ndev = self.mesh.devices.size
        local_n, block = _shard_widths(self, ruleno, result_max,
                                       -(-n // ndev))
        self._count_take_plans(ruleno, ndev * -(-local_n // block))


def _tunables_key(t):
    return (t.choose_total_tries, t.chooseleaf_descend_once,
            t.chooseleaf_vary_r, t.chooseleaf_stable)


@functools.lru_cache(maxsize=256)
def _compiled_rule(steps, result_max, tkey, max_depth, present,
                   type_depth=(), tree_depth=0, flags=(False, False)):
    PERF.inc("rule_compiles")            # body runs only on an lru miss
    return jax.jit(_rule_body(steps, result_max, tkey, max_depth, present,
                              type_depth, tree_depth, flags))


# ids one-hot-encoded per MXU pass of _count_placements: a bin of one
# chunk counts at most this many, far below 2^24, so the f32 sum is exact
_COUNT_CHUNK = 1 << 13
_COUNT_SHIFT = 7
_COUNT_LANES = 1 << _COUNT_SHIFT
# columns of a (block, rmax) result counted in one pass: a wider result
# is counted eight columns at a time (see _count_placements)
_COUNT_COLS = 8


def _count_placements(flat, nbins):
    """Histogram of int32 ids in [0, nbins) -> int32[nbins], exact, with
    no scatter: id = hi * 128 + lo, a chunk of ids becomes two bf16
    one-hots, (chunk, ceil(nbins / 128)) of hi and (chunk, 128) of lo,
    and their product over the chunk axis (one MXU pass, f32 sum) is
    that chunk's counts laid out (hi, lo); a scan adds the chunks up in
    int32. Ids outside [0, nbins) count nowhere. On a v5e a 2^21 x 3
    block takes 8.6 ms whatever its ids; the scatter-add this replaced
    serialises on colliding ids: 522-607 ms into int64 bins (88% of a
    sweep), 42-49 ms into int32; sort-and-difference 12.5 ms (PERF.md).

    ``flat`` may have any shape of fewer than 2^31 elements. A 2-D
    ``flat`` wider than ``_COUNT_COLS`` is counted that many columns at
    a time: on a v5e one pass over a (2^20, 10) or (2^20, 11) block lost
    128 ids, one vector row, where (2^20, 8) and every narrower block
    lose none (PERF.md, PR 33); three columns lower as they did."""
    if flat.ndim == 2 and flat.shape[1] > _COUNT_COLS:
        return sum(_count_placements(flat[:, lo:lo + _COUNT_COLS], nbins)
                   for lo in range(0, flat.shape[1], _COUNT_COLS))
    # column-major: a (block, rmax) result lives lane-major on the TPU,
    # where a row-major flatten first pads rmax to 128 lanes
    ids = flat.T.reshape(-1)
    n = ids.shape[0]
    rows = -(-nbins // _COUNT_LANES)
    chunk = min(_COUNT_CHUNK, -(-n // _COUNT_LANES) * _COUNT_LANES)
    ids = jnp.pad(ids, (0, -n % chunk), constant_values=-1)
    hi_iota = jnp.arange(rows, dtype=jnp.int32)
    lo_iota = jnp.arange(_COUNT_LANES, dtype=jnp.int32)

    def add_chunk(acc, c):
        hi = c >> _COUNT_SHIFT      # arithmetic: the -1 padding has no row
        lo = c & (_COUNT_LANES - 1)
        a = (hi[:, None] == hi_iota).astype(jnp.bfloat16)
        b = (lo[:, None] == lo_iota).astype(jnp.bfloat16)
        m = jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return acc + m.astype(jnp.int32), None

    acc, _ = jax.lax.scan(
        add_chunk, jnp.zeros((rows, _COUNT_LANES), dtype=jnp.int32),
        ids.reshape(-1, chunk))
    return acc.reshape(-1)[:nbins]


# what an indep sweep's ``bad`` vector carries after the bad mappings,
# under the names ``PERF`` counts them by. A round costs the width it
# runs at: ``indep_lane_rounds_run`` over ``indep_rounds`` is a round's
# mean width (on the 10,240-OSD map's 11-wide rule 1.14 x 2^20 over 4:
# one full round, one an eighth as wide, two a 128th), and
# ``indep_lane_rounds_needed`` over it the share of that which had
# anything to place.
INDEP_TALLY = ("indep_blocks", "indep_rounds", "indep_lane_rounds_needed",
               "indep_lane_rounds_run", "indep_blocks_narrowed",
               "indep_holes")
# what a kernel sweep's ``bad`` vector carries after the bad mappings
# where the plan draws inside a margin (``_make_kernel_body``): of each
# block, the lanes the kernel flagged, whether the compact recompute
# ran, and whether the flags overflowed its buffer
KERNEL_TALLY = ("kernel_flagged_lanes", "kernel_fallback_blocks",
                "kernel_fallback_overflows")
# what a firstn sweep on the rule VM (a rule with no kernel plan: a
# shape ``build_plan`` does not take, or any rule off the TPU)
# carries after the bad mappings: of each block, the lane-slots its
# firstn blocks ran, those the speculative tries left to
# ``_choose_one_firstn``'s loop, and the rounds that loop ran at full
# width, summed over slots. A slot's loop costs its unluckiest lane.
FIRSTN_TALLY = ("firstn_slots", "firstn_loop_lanes", "firstn_loop_rounds")


def fallback_lanes(n: int) -> int:
    """Lanes of the buffer a kernel block of ``n`` lanes gathers its
    flagged lanes into for the bit-exact recompute: a 256th of the
    block, 8,192 at 2^21 lanes; a block that flags more takes as many
    passes of it as it needs and is counted
    (``kernel_fallback_overflows``)."""
    return min(n, max(256, n >> 8))


@functools.lru_cache(maxsize=256)
def _compiled_sweep(fn_body, tally, n_devices, block, result_max):
    """Per-block aggregated sweep step: map one x block, count its
    placements per device on device (``_count_placements``: no scatter,
    the colliding scatter-add that stood here took 88% of a v5e's time)
    and fold them into the running int64 counts with one add (the
    CrushTester aggregation, without the (N, rep) device->host ship of
    round 1). The host loops over blocks — dispatches are async, so
    consecutive blocks pipeline and only the final count readback
    synchronizes. The step maps ``x0 + arange(block)`` in full and
    masks the lanes past ``remaining`` only when it counts: kernel,
    flagged-lane fallback and counting all cost the block's width, not
    the lanes asked for, so the caller picks ``block`` from the lanes
    it has left (``Mapper._block_for``). On a v5e the program at 2^21
    lanes takes 77.7 ms, of it the kernel 59.8 (PERF.md).

    counts has n_devices+1 bins: the last collects ITEM_NONE/out-of-range
    lanes and is dropped by the caller. With a ``tally`` the body
    returns ``(mappings, stats)`` and ``bad`` is an int64 vector: the
    bad mappings, then the tally's counters. ``INDEP_TALLY``: the body
    is ``_rule_body(..., tally=INDEP_TALLY)``, and the step adds the
    holes. ``KERNEL_TALLY``: ``Mapper._kernel_body(..., tally=True)``.
    ``FIRSTN_TALLY``: ``_rule_body(..., tally=FIRSTN_TALLY)``. An indep step
    costs its rounds, and a round the width it runs at
    (``_choose_indep_block``): the first the block's, the later ones an
    eighth and then a 128th of it once no more lanes than that are
    unfilled."""
    PERF.inc("sweep_compiles")           # body runs only on an lru miss

    def run(arrs, counts, bad, x0, remaining):
        xs = x0 + jnp.arange(block, dtype=jnp.uint32)
        inb = jnp.arange(block, dtype=jnp.int64) < remaining
        w = fn_body(arrs, xs)                         # (block, rmax) int32
        if tally:
            w, stats = w
        live = w != ITEM_NONE
        flat = jnp.where(live & inb[:, None], w, n_devices)
        counts = counts + _count_placements(
            flat, n_devices + 1).astype(jnp.int64)
        # a bad mapping is upstream's: fewer than result_max entries
        # or an ITEM_NONE among them (an indep rule's hole)
        short = (live.sum(axis=1) < result_max) & inb
        if not tally:
            return counts, bad + short.sum(dtype=jnp.int64)
        if tally != INDEP_TALLY:             # KERNEL_TALLY, FIRSTN_TALLY
            return counts, bad + jnp.concatenate([
                short.sum(dtype=jnp.int64)[None], stats.astype(jnp.int64)])
        # ``bad`` is the indep tally: bad mappings, then INDEP_TALLY
        holes = (~live & inb[:, None]).sum(dtype=jnp.int64)
        return counts, bad + jnp.concatenate([
            short.sum(dtype=jnp.int64)[None], stats.astype(jnp.int64),
            holes[None]])

    return jax.jit(run, donate_argnums=(1,))


def _depth_between(type_depth, from_type, to_type):
    """Static descent level count on uniform hierarchies, else None."""
    if (from_type is None or to_type is None
            or not (0 <= to_type < len(type_depth))
            or not (0 <= from_type < len(type_depth))):
        return None
    df, dt = type_depth[from_type], type_depth[to_type]
    if df <= 0 or dt < 0 or df <= dt:
        return None
    return df - dt


@functools.lru_cache(maxsize=256)
def _rule_body(steps, result_max, tkey, max_depth, present, type_depth=(),
               tree_depth=0, flags=(False, False), tally=()):
    """The rule VM: ``run(arrs, xs) -> (n, result_max)`` mappings.
    With a ``tally`` it returns ``(mappings, stats)``, stats the int32
    vector the sweep step tallies under those names. ``INDEP_TALLY``:
    choose_indep blocks run, then the sum of their
    ``_choose_indep_block`` tallies (rounds, needed lane-rounds,
    lane-rounds run, blocks narrowed). ``FIRSTN_TALLY``: the sum of the
    firstn blocks' ``_choose_firstn_block`` tallies."""
    firstn_stats = tally == FIRSTN_TALLY
    total_tries, descend_once, vary_r, stable = tkey
    base_cfg = {"max_depth": max_depth, "present": present,
                "tree_depth": tree_depth,
                "all_uniform": flags[0], "skip_is_out": flags[1]}

    def run(arrs, xs):
        n = xs.shape[0]
        B = arrs["size"].shape[0]
        choose_tries = total_tries
        choose_leaf_tries = 0
        vr = vary_r
        # Working set: list of (values (N,), is_leaf_col) columns.
        w_cols: list = []
        emitted: list = []
        any_firstn = False
        stats = jnp.zeros(len(FIRSTN_TALLY) if firstn_stats
                          else len(INDEP_TALLY) - 1, dtype=jnp.int32)
        cur_type = None   # static type of the current columns' items
        for step in steps:
            op, arg1, arg2 = step[0], step[1], step[2]
            if op == OP_NOOP:
                continue
            if op == OP_TAKE:
                w_cols = [jnp.full(n, arg1, dtype=jnp.int32)]
                cur_type = step[3] if len(step) > 3 else None
            elif op == OP_SET_CHOOSE_TRIES:
                if arg1 > 0:
                    choose_tries = arg1
            elif op == OP_SET_CHOOSELEAF_TRIES:
                if arg1 > 0:
                    choose_leaf_tries = arg1
            elif op == OP_SET_CHOOSELEAF_VARY_R:
                if arg1 >= 0:
                    vr = arg1
            elif op == OP_SET_CHOOSELEAF_STABLE:
                if arg1 >= 0 and arg1 != 1:
                    raise NotImplementedError("stable=0 unsupported")
            elif op in (OP_SET_CHOOSE_LOCAL_TRIES,
                        OP_SET_CHOOSE_LOCAL_FALLBACK_TRIES):
                if arg1 > 0:
                    raise NotImplementedError("local retries unsupported")
            elif op in (OP_CHOOSE_FIRSTN, OP_CHOOSELEAF_FIRSTN,
                        OP_CHOOSE_INDEP, OP_CHOOSELEAF_INDEP):
                firstn = op in (OP_CHOOSE_FIRSTN, OP_CHOOSELEAF_FIRSTN)
                recurse = op in (OP_CHOOSELEAF_FIRSTN, OP_CHOOSELEAF_INDEP)
                any_firstn = any_firstn or firstn
                numrep = arg1 if arg1 > 0 else arg1 + result_max
                if firstn:
                    recurse_tries = (choose_leaf_tries or
                                     (1 if descend_once else choose_tries))
                else:
                    recurse_tries = choose_leaf_tries or 1
                # exact static descent depths on uniform hierarchies
                cfg = dict(base_cfg)
                cfg["levels_main"] = _depth_between(type_depth, cur_type,
                                                    arg2)
                cfg["levels_leaf"] = (_depth_between(type_depth, arg2, 0)
                                      if recurse else None)
                new_cols = []
                osize = 0
                for col in w_cols:
                    if osize >= result_max:
                        break
                    root_valid = (col < 0) & (-1 - col < B)
                    root_rows = jnp.clip(-1 - col, 0, B - 1)
                    if firstn:
                        blk = min(numrep, result_max - osize)
                        out, leaves, *block_stats = _choose_firstn_block(
                            arrs, cfg, root_rows, root_valid, xs, blk,
                            arg2, recurse, choose_tries, recurse_tries, vr,
                            tally=firstn_stats)
                        if firstn_stats:
                            stats = stats + block_stats[0]
                    else:
                        blk = min(numrep, result_max - osize)
                        out, leaves, block_stats = _choose_indep_block(
                            arrs, cfg, root_rows, root_valid, xs, blk,
                            numrep, arg2, recurse, choose_tries,
                            recurse_tries)
                        stats = stats + jnp.concatenate(
                            [jnp.ones(1, dtype=jnp.int32), block_stats])
                    chosen = leaves if recurse else out
                    # Device roots with matching type pass through.
                    if arg2 == 0:
                        passthrough = (col >= 0)
                        chosen = jnp.where(passthrough[:, None],
                                           jnp.where(
                                               jnp.arange(blk)[None, :] == 0,
                                               col[:, None],
                                               ITEM_NONE),
                                           chosen)
                    for j in range(blk):
                        new_cols.append(chosen[:, j])
                    osize += blk
                w_cols = new_cols
                cur_type = 0 if recurse else arg2
            elif op == OP_EMIT:
                emitted.extend(w_cols)
                w_cols = []
            else:
                raise NotImplementedError(f"rule op {op}")
        if not emitted:
            emitted = w_cols
        w = (jnp.stack(emitted, axis=1) if emitted
             else jnp.full((n, result_max), ITEM_NONE, dtype=jnp.int32))
        if any_firstn:
            w = _compact(w)
        if w.shape[1] < result_max:
            pad = jnp.full((n, result_max - w.shape[1]), ITEM_NONE,
                           dtype=jnp.int32)
            w = jnp.concatenate([w, pad], axis=1)
        w = w[:, :result_max]
        return (w, stats) if tally else w

    return run

"""Pod-scale CRUSH: the mapping sweep sharded over a device mesh.

The single-device engine (``mapper.Mapper``) streams PG blocks through
one chip; the paper's pod-scale claim ("<1 s for 100M PGs on a v5e-8")
was, until round 10, an ESTIMATE built on a linearity assumption that
had never run on real ICI. This module is the missing first-class
layer: the PG-id batch is the data-parallel axis of a
``jax.sharding.Mesh`` (``shard_map`` over the ``shard`` axis), and the
sweep runs SPMD with

- **replicated map tensors**: the packed CRUSH arrays (a few MiB even
  at 10k OSDs) ride every device whole (``in_specs=P()``) — the map is
  the only shared state of CRUSH (SURVEY.md §5.8), and replicating it
  is what keeps the hot path collective-free;
- **per-shard iota**: each device derives its own PG-id range from
  ``axis_index`` — nothing O(n_pgs) is ever materialized globally, so
  the sweep scales to the 100M-PG target without a host-side array in
  sight;
- **zero collectives on the hot path**: mapping is per-PG-independent,
  so the ONLY communication in the aggregated sweep is one
  ``(max_devices,)`` ``psum`` of the per-device placement counts at
  the very end, each shard having counted its own placements as the
  single-device step does (``mapper._count_placements``, a one-hot
  matmul: the scatter-add it replaced took nine tenths of a v5e's
  time); ``sharded_map_pgs`` has none at all — its output stays
  sharded on the batch axis until the caller reads it back.

Both entry points serve whichever engine the single-device path would
use — the fused Pallas kernel body (with its masked XLA fallback for
ambiguity-flagged lanes) when the rule is eligible, the XLA rule VM
otherwise — so the sharded result is BIT-EXACT against
``Mapper.map_pgs``/``Mapper.sweep`` lane for lane, including the
flagged-lane recomputations (each shard runs the identical per-lane
program; tests/test_sharded_sweep.py pins it across shard boundaries,
non-divisible batches, zero-weight slots and choose_args weight-sets).

Non-divisible batches pad: ``sharded_map_pgs`` pads the PG-id batch up
to a device multiple and strips the padding after the gather;
``sharded_sweep`` gives every shard the same (ceil) local range and
masks the tail lanes out of the count accumulation.

Wiring: ``Mapper(mesh=...)`` (or ``Mapper.attach_mesh``) routes
``sweep``/``map_pgs`` batches of at least ``mesh_min_batch`` lanes
through this module; ``osd/osdmap_mapping.py`` full-pool sweeps reuse
it when a mesh is attached to the mapping (the
``remap_sharded_sweeps`` perf counter records each one).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ceph_tpu.utils import tracing
from ceph_tpu.utils.devmon import devmon as _devmon


# Below this many lanes the per-shard dispatch overhead outweighs the
# parallelism (the crossover is not re-measured on a local chip);
# Mapper delegation and OSDMapMapping full sweeps
# stay single-device for smaller batches. Overridable per Mapper
# (mesh_min_batch) — tests lower it to exercise the sharded path on
# small pools.
MESH_MIN_BATCH = 1 << 16


def _mesh_axis(mesh):
    return mesh.axis_names[0]


def _shard_widths(mapper, ruleno: int, result_max: int,
                  local_n: int) -> tuple[int, int]:
    """``(local_n, block)`` of a shard program that maps ``local_n``
    lanes a shard. Every distinct pair compiles (and, on the kernel
    path, caches) its own shard program, so a shard of one tile runs
    it at ``mapper.block_width``'s power of two, as the single-device
    sweep does: at most log2(cap) shapes, under 2x the lanes on batches
    that are small anyway. Wider shards keep their exact width and
    tile it by the rule's widest block, so the 100M-PG sweep pays no
    padding (its sizes are stable per pool/bench anyway)."""
    from ceph_tpu.crush.mapper import block_width
    block = block_width(local_n, mapper.effective_block(ruleno, result_max))
    return max(local_n, block), block


def _fn_body(mapper, ruleno: int, result_max: int):
    """The per-block mapping body the single-device path would run:
    the fused kernel body (with its bit-exact flagged-lane fallback)
    when eligible, else the XLA rule VM. Returns (fn, used_kernel)."""
    from ceph_tpu.crush.mapper import _rule_body
    kb = mapper._kernel_body(ruleno, result_max)
    if kb is not None:
        return kb, True
    return _rule_body(*mapper._rule_key(ruleno, result_max)), False


def _shard_fn(mapper, used_kernel, compile_fn, *key):
    """Compiled-shard-program cache routing. XLA rule bodies are
    process-shared objects (mapper._rule_key-lru'd), so their
    shard_map wrappers cache globally and HIT across Mapper instances
    (the OSDMapMapping decode-fresh-map-per-epoch path). Kernel bodies
    are per-Mapper closures over the plan tables — caching those
    globally would both miss every fresh Mapper AND pin up to maxsize
    retired Mappers' plans alive through the closure, so they cache ON
    the mapper and die with it."""
    if not used_kernel:
        return compile_fn(*key)
    cache = mapper.__dict__.setdefault("_sharded_fns", {})
    fn = cache.get(key)
    if fn is None:
        fn = compile_fn.__wrapped__(*key)
        cache[key] = fn
    return fn


@functools.lru_cache(maxsize=64)
def _compiled_sharded_map(fn_body, mesh, block, local_n, result_max):
    """shard_map'd full-mapping step: map tensors replicated, the PG-id
    batch sharded; each shard walks its local range in block-sized
    tiles (bounding straw2 temps exactly like the single-device path).
    No collectives — the output stays sharded on the batch axis."""
    axis = _mesh_axis(mesh)

    def local(arrs, xs):
        outs = []
        for lo in range(0, local_n, block):
            width = min(block, local_n - lo)
            outs.append(fn_body(arrs, xs[lo:lo + width]))
        return outs[0] if len(outs) == 1 else \
            jnp.concatenate(outs, axis=0)

    # check_vma off: the rule VM's while_loop carries state from
    # unvarying constants, which the varying-manual-axes checker
    # rejects even though the computation is correctly per-shard
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis)),
        out_specs=P(axis),
        check_vma=False))


def sharded_map_pgs(mesh, mapper, ruleno: int, xs,
                    result_max: int) -> jax.Array:
    """Vectorized crush_do_rule over ``xs`` with the batch sharded over
    the mesh -> (N, result_max) int32, bit-exact vs Mapper.map_pgs.

    ``xs`` may be any length: the batch pads up to a device multiple
    (pad lanes recompute lane xs[0]; their rows are stripped before
    return)."""
    if getattr(mapper, "_scalar_reason", None):
        raise ValueError(
            f"map uses legacy tunables ({mapper._scalar_reason}); the "
            f"scalar fallback cannot shard — use Mapper.map_pgs")
    ndev = mesh.devices.size
    with jax.enable_x64(True):
        xs = jnp.asarray(xs, dtype=jnp.uint32)
        n = xs.shape[0]
        if n == 0:
            return jnp.zeros((0, result_max), dtype=jnp.int32)
        local_n, block = _shard_widths(mapper, ruleno, result_max,
                                       -(-n // ndev))
        pad = local_n * ndev - n
        if pad:
            xs = jnp.concatenate(
                [xs, jnp.broadcast_to(xs[0], (pad,))])
        fn_body, used_kernel = _fn_body(mapper, ruleno, result_max)
        fn = _shard_fn(mapper, used_kernel, _compiled_sharded_map,
                       fn_body, mesh, block, local_n, result_max)
        out = _devmon().jit_call(
            "crush_sharded_map",
            mapper._jit_key(ruleno, result_max, used_kernel,
                            ("sharded", local_n, block)),
            fn, mapper.arrays, xs)
        mapper.last_map_path = \
            mapper.mapping_path(ruleno, result_max) + "+sharded"
        return out[:n] if pad else out


@functools.lru_cache(maxsize=64)
def _compiled_sharded_sweep(fn_body, nd, mesh, block, local_n, result_max):
    """shard_map'd aggregated sweep step: per-shard iota + local
    counts through the single-device step's ``_count_placements`` (no
    scatter: the colliding scatter-add that stood here took 752 of a
    v5e's 825 ms a sweep), ONE psum pair at the end — the whole
    communication cost of scaling CRUSH."""
    axis = _mesh_axis(mesh)
    from ceph_tpu.crush.mapper import _count_placements
    from ceph_tpu.crush.types import ITEM_NONE

    def local(arrs, start_x, n_total):
        # per-shard iota: nothing of O(n) is ever materialized globally
        me = jax.lax.axis_index(axis)
        base = start_x + me.astype(jnp.uint32) * jnp.uint32(local_n)
        # this shard's live lane count (the ceil split leaves the last
        # shards short when n does not divide)
        remaining = jnp.clip(n_total - me.astype(jnp.int64)
                             * jnp.int64(local_n),
                             jnp.int64(0), jnp.int64(local_n))
        counts = jnp.zeros(nd + 1, dtype=jnp.int64)
        bad = jnp.int64(0)
        for lo in range(0, local_n, block):      # static tile loop
            xs = base + jnp.uint32(lo) + jnp.arange(block,
                                                    dtype=jnp.uint32)
            inb = (jnp.int64(lo)
                   + jnp.arange(block, dtype=jnp.int64)) < remaining
            w = fn_body(arrs, xs)                # (block, rmax)
            live = (w != ITEM_NONE) & inb[:, None]
            flat = jnp.where(live, w, nd)
            counts = counts + _count_placements(
                flat, nd + 1).astype(jnp.int64)
            # upstream's bad mapping: short, or an indep rule's hole
            short = (live.sum(axis=1) < result_max) & inb
            bad = bad + short.sum(dtype=jnp.int64)
        return (jax.lax.psum(counts[:nd], axis),
                jax.lax.psum(bad, axis))

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False))


def sharded_sweep(mesh, mapper, ruleno: int, start_x: int, n: int,
                  result_max: int):
    """Aggregated CRUSH sweep of [start_x, start_x + n) with the PG
    range sharded over the mesh — the multi-chip Mapper.sweep.

    Any ``n`` is accepted (tail lanes mask out of the accumulation);
    the range wraps modulo 2^32 as ``Mapper.sweep``'s does.
    Returns (counts (max_devices,), bad) replicated on every device,
    equal to the single-device sweep's, unread: the caller's read-back
    is the sweep's one sync. The call is the section ``crush.sweep``
    (tags ``lanes``, ``takes``, ``blocks`` a shard, ``width``), the
    program's call
    a ``crush.dispatch`` (as ``Mapper.sweep_path``'s)."""
    if getattr(mapper, "_scalar_reason", None):
        raise ValueError(
            f"map uses legacy tunables ({mapper._scalar_reason}); the "
            f"scalar fallback cannot shard — use Mapper.sweep")
    with tracing.section("crush.sweep", service="crush") as sec:
        ndev = mesh.devices.size
        nd = mapper.packed.max_devices
        local_n, block = _shard_widths(mapper, ruleno, result_max,
                                       max(1, -(-n // ndev)))
        if sec:
            sec.tag("lanes", int(n)).tag("takes", mapper.takes(ruleno)) \
                .tag("blocks", -(-local_n // block)).tag("width", block)
        fn_body, used_kernel = _fn_body(mapper, ruleno, result_max)
        fn = _shard_fn(mapper, used_kernel, _compiled_sharded_sweep,
                       fn_body, nd, mesh, block, local_n, result_max)
        key = mapper._jit_key(ruleno, result_max, used_kernel,
                              ("sharded", local_n, block, nd))
        with jax.enable_x64(True):
            x0, total = jnp.uint32(start_x % (1 << 32)), jnp.int64(n)
            with tracing.section("crush.dispatch", service="crush") as d:
                if d:
                    d.tag("block", 0)
                out = _devmon().jit_call("crush_sharded_sweep", key, fn,
                                         mapper.arrays, x0, total)
        mapper.last_map_path = \
            mapper.mapping_path(ruleno, result_max) + "+sharded"
        return out

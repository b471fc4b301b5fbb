"""Multi-controller (multi-host) SPMD: the DCN communication story.

ref: the role of the reference's NCCL/MPI multi-host backend (SURVEY.md
§5.8). On TPU pods the transport hierarchy is ICI within a slice and
DCN between hosts; in JAX the same program runs on every host
(multi-controller SPMD), ``jax.distributed`` supplies the coordination
plane, and XLA inserts the cross-host collectives — there is no NCCL
ring to manage. This module is that story made concrete and testable
without pod hardware: N coordinated CPU processes, each with M virtual
devices, form a global (host, shard) mesh whose ``host`` axis IS the
DCN boundary.

Two framework pipelines run over the global mesh:

- EC encode with the stripe batch sharded over the ``host`` (DCN) axis
  — embarrassingly parallel, zero cross-host bytes on the hot path,
  which is exactly why EC striping scales to pods: only the checksum
  reduction crosses DCN.
- the aggregated CRUSH sweep over a 1-D mesh spanning every device of
  every host — its single ``psum`` of the (max_devices,) count vector
  is the entire cross-host communication cost of scaling placement.

Both are asserted bit-equal to the local single-process computation.

Run one worker per host (the test spawns two):

    python -m ceph_tpu.parallel.multihost --coordinator 127.0.0.1:PORT \
        --num-processes 2 --process-id {0,1}
"""

from __future__ import annotations

import argparse
import json
import os
import sys



def run_worker(coordinator: str, num_processes: int, process_id: int,
               local_devices: int = 4) -> dict:
    # CPU-coordinated workers: each is one of several processes on
    # this host, and a chip belongs to one process at a time, so the
    # pin below keeps every worker off it (it must precede any jax use).
    # APPEND to any existing XLA_FLAGS (a setdefault would silently
    # drop the device count — and with it --local-devices — whenever
    # the caller had unrelated flags set)
    flag = f"--xla_force_host_platform_device_count={local_devices}"
    prior = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prior:
        os.environ["XLA_FLAGS"] = f"{prior} {flag}".strip()
    else:
        import re as _re
        os.environ["XLA_FLAGS"] = _re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, prior)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator, num_processes, process_id)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ceph_tpu.crush import builder
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.ec import matrix as rs
    from ceph_tpu.gf import ops, tables
    from ceph_tpu.parallel.sharded import sharded_crush_sweep

    devs = jax.devices()
    assert len(devs) == num_processes * local_devices, len(devs)
    assert jax.process_count() == num_processes

    # --- DCN-aware 2-axis mesh: host axis == process boundary ---------
    by_proc: dict[int, list] = {}
    for d in devs:
        by_proc.setdefault(d.process_index, []).append(d)
    dev2d = np.array([by_proc[p] for p in sorted(by_proc)])
    mesh2 = Mesh(dev2d, ("host", "shard"))

    # --- EC over DCN: stripe batch split across hosts -----------------
    k, m, C, batch = 4, 2, 4096, 8 * num_processes
    coding = rs.coding_matrix("reed_sol_van", k, m)
    bitmatrix = jnp.asarray(tables.expand_bitmatrix(coding),
                            dtype=jnp.int8)
    lo, hi = map(jnp.asarray, tables.nibble_tables(coding))
    rng = np.random.default_rng(7)           # same stream on all hosts
    data_np = rng.integers(0, 256, size=(batch, k, C), dtype=np.uint8)
    sharding = NamedSharding(mesh2, P("host", None, None))
    data = jax.make_array_from_callback(
        data_np.shape, sharding, lambda idx: data_np[idx])

    @jax.jit
    def encode(d):
        out = ops.encode_stripes(bitmatrix, lo, hi, d,
                                 backend="bitmatmul")
        # uint32 with wraparound: deterministic, and x64 stays off
        return jax.lax.with_sharding_constraint(out, sharding), \
            jnp.sum(out.astype(jnp.uint32))

    parity, checksum = encode(data)
    jax.block_until_ready(parity)
    # every addressable shard holds exactly this host's DCN slice of
    # the batch (replicated across the host's own shard axis)
    assert all(s.data.shape[0] == batch // num_processes
               for s in parity.addressable_shards), \
        [s.data.shape for s in parity.addressable_shards]
    # ...and the replicated checksum matches a purely local encode
    ref = np.asarray(jax.jit(lambda: ops.encode_stripes(
        bitmatrix, lo, hi, jnp.asarray(data_np),
        backend="bitmatmul"))())
    assert int(jax.device_get(checksum)) == int(
        ref.astype(np.uint64).sum() & 0xFFFFFFFF), \
        "cross-host EC checksum mismatch"

    # --- CRUSH over the full global mesh ------------------------------
    mesh1 = Mesh(dev2d.reshape(-1), ("shard",))
    cm, root = builder.build_hierarchy(8, 8, n_racks=2)
    rid = builder.add_simple_rule(cm, root, builder.TYPE_HOST)
    mapper = Mapper(cm, block=1 << 9)
    # replicated operands must be global arrays in multi-controller
    with jax.enable_x64(True):
        mapper.arrays = jax.device_put(
            mapper.arrays, NamedSharding(mesh1, P()))
    n_pgs = 256 * len(devs)
    counts, bad = sharded_crush_sweep(mesh1, mapper, rid, 0, n_pgs, 3)
    got = np.asarray(counts)
    # local single-process reference on a fresh Mapper (local arrays)
    ref_counts, ref_bad = Mapper(cm, block=1 << 9).sweep(
        rid, 0, n_pgs, 3)
    assert (got == np.asarray(ref_counts)).all(), \
        "cross-host CRUSH counts diverge from the local sweep"
    assert int(bad) == int(ref_bad)
    assert int(got.sum()) == 3 * n_pgs

    return {"ok": True, "process_id": process_id,
            "processes": jax.process_count(),
            "global_devices": len(devs),
            "local_devices": local_devices,
            "ec_checksum": int(jax.device_get(checksum)),
            "crush_placements": int(got.sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="multihost")
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=4)
    args = ap.parse_args(argv)
    out = run_worker(args.coordinator, args.num_processes,
                     args.process_id, args.local_devices)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Device-scaling table: EC encode + CRUSH sweep at 1..N devices.

On a four-chip host it scales over the real mesh (``chip_smoke.py
--chips 4`` is the quick proof that the sharded paths run there). Under
the virtual CPU mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m ceph_tpu.bench.multichip

it only demonstrates the SPMD structure (the EC path has zero
collectives; the CRUSH sweep's only collective is one (max_devices,)
psum), not speed — virtual CPU devices share the host's cores.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np



def ec_rate(mesh, n_devices: int, batch: int, C: int) -> float:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ceph_tpu.ec import matrix as rs
    from ceph_tpu.gf import tables
    from ceph_tpu.parallel import sharded_encode

    k, m = 8, 3
    coding = rs.coding_matrix("reed_sol_van", k, m)
    bitmatrix = jnp.asarray(tables.expand_bitmatrix(coding), dtype=jnp.int8)
    lo, hi = map(jnp.asarray, tables.nibble_tables(coding))
    rng = np.random.default_rng(0)
    data = jax.device_put(
        jnp.asarray(rng.integers(0, 256, (batch, k, C), np.uint8)),
        NamedSharding(mesh, P(mesh.axis_names[0], None, None)))
    out = sharded_encode(mesh, bitmatrix, lo, hi, data)
    np.asarray(out[0, 0, :1])            # sync
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = sharded_encode(mesh, bitmatrix, lo, hi, data)
        np.asarray(out[0, 0, :1])
        best = min(best, time.perf_counter() - t0)
    return batch * k * C / best


def measured_sweep(mesh, mapper, n_pgs: int, num_rep: int = 3,
                   rule: int = 0, reps: int = 2) -> dict:
    """The crush_multichip bench record: wall time of ONE full
    aggregated sharded sweep of ``n_pgs``, readback-anchored.

    ``measured: true`` means exactly that — the reported wall covers a
    real execution of every PG in ``n_pgs`` on this mesh, not a
    two-size slope and not the single-chip-rate-times-N linearity
    assumption the paper's pod estimate rested on (ROADMAP open item
    #1). When ``n_pgs`` is below 100M, ``seconds_100M`` is the
    measured wall rescaled and ``extrapolated: true`` says so; the
    driver bench runs the full 100M (``extrapolated: false``), making
    ``seconds_100M`` the measured pod wall time itself."""
    import jax
    from ceph_tpu.crush.sharded_sweep import sharded_sweep

    counts, bad = sharded_sweep(mesh, mapper, rule, 0, n_pgs,
                                num_rep)            # warm + compile
    np.asarray(counts)
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        counts, bad = sharded_sweep(mesh, mapper, rule, 0, n_pgs,
                                    num_rep)
        np.asarray(counts)                          # D2H anchor
        best = min(best, time.perf_counter() - t0)
    return {
        "metric": "crush_multichip",
        "measured": True,
        "n_devices": int(mesh.devices.size),
        "n_pgs": int(n_pgs),
        "num_rep": num_rep,
        "n_osds": int(mapper.packed.max_devices),
        "seconds_wall": round(best, 3),
        "mappings_per_s": round(n_pgs / best, 1),
        "seconds_100M": round(best * (1e8 / n_pgs), 3),
        "extrapolated": bool(n_pgs < 100_000_000),
        "bad_mappings": int(bad),
        "placements": int(np.asarray(counts).sum()),
        "path": mapper.last_map_path,
        "platform": jax.devices()[0].platform,
    }


def crush_rate(mesh, mapper, n_pgs: int) -> float:
    from ceph_tpu.parallel import sharded_crush_sweep

    counts, _ = sharded_crush_sweep(mesh, mapper, 0, 0, n_pgs, 3)
    np.asarray(counts)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        counts, _ = sharded_crush_sweep(mesh, mapper, 0, 0, n_pgs, 3)
        np.asarray(counts)
        best = min(best, time.perf_counter() - t0)
    return n_pgs / best


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(prog="multichip_bench")
    ap.add_argument("--max-devices", type=int, default=0,
                    help="0 = all available")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=64 << 10)
    ap.add_argument("--crush-pgs", type=int, default=1 << 15)
    args = ap.parse_args(argv)

    import jax

    from ceph_tpu.bench.crush_sweep import canonical_map
    from ceph_tpu.crush.mapper import Mapper
    from ceph_tpu.parallel import make_mesh

    all_devices = jax.devices()
    maxd = args.max_devices or len(all_devices)
    mapper = Mapper(canonical_map(1024),
                    block=max(1024, args.crush_pgs // maxd))
    rows = []
    sizes = []
    d = 1
    while d < maxd:
        sizes.append(d)
        d *= 2
    sizes.append(maxd)          # always include the full device count
    for d in sizes:
        mesh = make_mesh(all_devices[:d])
        ec = ec_rate(mesh, d, args.batch, args.chunk)
        n_pgs = args.crush_pgs - args.crush_pgs % d   # shardable count
        cr = crush_rate(mesh, mapper, n_pgs)
        rows.append({"devices": d,
                     "ec_encode_MBps": round(ec / 1e6, 1),
                     "crush_mappings_per_s": round(cr, 1)})
        print(json.dumps(rows[-1]), flush=True)
    out = {"platform": all_devices[0].platform, "table": rows}
    # the measured (not slope, not extrapolated-linearity) full-mesh
    # record — the crush_multichip schema bench.py/test_meta pin
    out["crush_multichip"] = measured_sweep(
        make_mesh(all_devices[:maxd]), mapper, args.crush_pgs)
    if len(rows) > 1:
        out["ec_scaling"] = round(rows[-1]["ec_encode_MBps"]
                                  / rows[0]["ec_encode_MBps"], 2)
        out["crush_scaling"] = round(
            rows[-1]["crush_mappings_per_s"]
            / rows[0]["crush_mappings_per_s"], 2)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    from ceph_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

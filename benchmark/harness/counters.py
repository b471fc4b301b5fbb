"""The program's counters a run reads: a flat snapshot before and
after the window, and their difference.

``devmon.*`` is ``utils/devmon``'s device-runtime family, ``mapper.*``
is ``crush/mapper.PERF``; a driver adds the counters of the daemons it
holds (``agg.*``, ``read_agg.*`` and ``resident.*``, the device shard
cache's, summed over the OSDs). The health names are
``chip_smoke.py``'s: any of them moving means an answer came from a
host fallback, so a byte compare alone would pass with the chip's
kernels dead.
"""

from __future__ import annotations

DEVMON_KEYS = ("jit_compiles", "jit_compile_seconds", "launches_pallas",
               "launches_xla", "launches_scalar", "launches_sharded",
               "path_mismatch", "h2d_bytes", "d2h_bytes",
               "quarantine_entries", "stream_fallbacks")
AGG_KEYS = ("batches", "stripes", "ops", "bypass", "flush_window",
            "flush_full", "flush_idle")
RESIDENT_KEYS = ("hits", "misses", "inserts", "evictions", "entries")
AGG_BAD = ("fallback_ops", "crc_fallbacks", "per_op_retries",
           "flush_failures", "quarantined_ops")
HEALTH = tuple(f"{fam}.{k}" for fam in ("agg", "read_agg") for k in AGG_BAD) \
    + ("devmon.quarantine_entries", "devmon.path_mismatch",
       "devmon.stream_fallbacks", "mapper.kernel_exec_failures")


def _avg(d: dict, key: str) -> tuple[float, float]:
    v = d.get(key) or {}
    return float(v.get("avgcount", 0)), float(v.get("sum", 0.0))


def snapshot(osds=()) -> dict:
    from ceph_tpu.crush.mapper import PERF as mapper_perf
    from ceph_tpu.utils.devmon import devmon

    d = devmon().perf.dump()
    out = {f"devmon.{k}": float(d.get(k, 0)) for k in DEVMON_KEYS}
    out["mapper.kernel_exec_failures"] = float(
        mapper_perf.dump().get("kernel_exec_failures", 0))
    # which program a first call inside the window belonged to
    for fn, ent in devmon().dump().get("compiles_by_fn", {}).items():
        out[f"devmon.first_calls.{fn}"] = float(ent["count"])
    for fam, attr in (("agg", "ec_agg"), ("read_agg", "ec_read_agg")):
        tot = dict.fromkeys(AGG_KEYS + AGG_BAD, 0.0)
        wait_n = wait_s = 0.0
        for o in osds:
            p = getattr(o, attr).perf.dump()
            for k in tot:
                tot[k] += p.get(k, 0)
            n, s = _avg(p, "batch_wait")
            wait_n, wait_s = wait_n + n, wait_s + s
        for k, v in tot.items():
            out[f"{fam}.{k}"] = v
        out[f"{fam}.batch_wait_n"] = wait_n
        out[f"{fam}.batch_wait_s"] = wait_s
    # the device shard cache of each OSD (``entries`` is a gauge: its
    # delta is how far the caches filled or drained over the window)
    for k in RESIDENT_KEYS:
        out[f"resident.{k}"] = float(sum(
            o.ec_resident.perf.dump().get(k, 0) for o in osds
            if getattr(o, "ec_resident", None) is not None))
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0.0) for k in after}


def fallbacks(d: dict) -> int:
    """Sum of the health counters in a delta."""
    return int(sum(d.get(k, 0) for k in HEALTH))

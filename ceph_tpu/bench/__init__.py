"""Benchmark harnesses mirroring the reference's tools.

- ``ec_benchmark``: flag-compatible with ceph_erasure_code_benchmark
  (ref: src/test/erasure-code/ceph_erasure_code_benchmark.cc).
- ``crush_tester`` / crushtool CLI: the ``crushtool --test`` engine
  (ref: src/crush/CrushTester.cc, src/tools/crushtool.cc).
"""


def device_stamp() -> dict:
    """The device a record was taken on, as JAX reports it — every
    bench record carries this, so a CPU smoke-shape number can never be
    read as a chip number."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}

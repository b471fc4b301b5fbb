"""Record the small device trace that test_trace_reduce.py reads.

    chiprun -- python benchmark/tests/record_trace.py

Runs on the chip only: three EC encode launches under
``jax.profiler``, a host annotation around each, and a sleep after each
so that the trace has idle gaps of known length.
Writes ``chiprun_out/trace_fixture/`` (the ``.xplane.pb`` and a listing
of its planes, lines and first events); the fixture kept beside the
test is a copy of that file.
"""

import glob
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from ceph_tpu.ec import factory

    ec = factory("plugin=jax technique=reed_sol_van k=8 m=3")
    data = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, (128, 8, 4096), dtype=np.uint8))
    jax.block_until_ready(ec.encode_batch(data))

    out = ROOT / "chiprun_out" / "trace_fixture"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    marks = []
    jax.profiler.start_trace(str(out), profiler_options=opts)
    stretch = jax.profiler.TraceAnnotation("bench.stretch")
    stretch.__enter__()
    t0 = time.perf_counter_ns()
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.encode"):
            a = time.perf_counter_ns()
            jax.block_until_ready(ec.encode_batch(data))
            marks.append(("encode", a - t0, time.perf_counter_ns() - t0))
        time.sleep(0.02)
    stretch.__exit__(None, None, None)
    jax.profiler.stop_trace()
    pb = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(pb, out / "small.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(pb)
    listing = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            listing.append({
                "plane": plane.name, "line": line.name, "events": len(evs),
                "first": [[e.name[:80], e.start_ns, e.duration_ns,
                           {k: str(v)[:60] for k, v in list(e.stats)[:6]}]
                          for e in evs[:6]]})
    (out / "listing.json").write_text(json.dumps(
        {"marks": marks, "size": os.path.getsize(pb), "lines": listing},
        indent=1))
    for row in listing:
        print(row["plane"], "|", row["line"], "|", row["events"])
    print("size", os.path.getsize(pb), "marks", marks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

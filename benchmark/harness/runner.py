"""One run of one cell: the part that is the same whatever the cell.

``main`` reads ``BENCHMARK.json``, finds the cell's configuration file,
its traffic file and the driver the traffic names, takes the devices,
lets the driver set up, measure and compare, reads the per-layer metrics
through their readers, and prints the contract's last line. It names no
cell, configuration or metric.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

from . import counters, peaks as peaks_mod, trace_reduce

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a chip with no peaks."""


def _load_py(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} is missing")
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _by_name(rows, name, what):
    for r in rows:
        if r["name"] == name:
            return r
    raise SystemExit(f"BENCHMARK.json has no {what} {name!r}")


class Compared:
    """The numbers that decide ``correct``, each beside its limit. A
    number passes when it is at most its limit."""

    def __init__(self):
        self.rows = {}

    def add(self, name: str, value, limit) -> None:
        self.rows[name] = {"value": value, "limit": limit}

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(
            r["value"] is not None and r["value"] <= r["limit"]
            for r in self.rows.values())


class Context:
    """What a driver and the metric readers see of a run."""

    def __init__(self, spec, cell, config, traffic, args, t_start):
        self.spec, self.cell = spec, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace_on = bool(args.trace)
        self.rehearsal = args.rehearsal
        self.t_start = t_start
        self.phases = {}                 # the set-up split, seconds
        self.obs = {}                    # the driver's own timings
        self.delta = {}                  # counter deltas over the window
        self.compared = Compared()
        self.trace = None                # TraceSummary of the stretch
        self.trace_span = None           # (t0, t1) host clock, seconds
        self.setup_s = None
        self.window_s = None
        self.attempted = self.failed = 0
        self.values = {}                 # end-to-end metric -> value
        self.devices, self.peaks = [], None
        self.memory_peak_bytes = None
        self.osds = ()                   # daemons whose counters count
        self.at_open = None             # the counters at the opening
        self._trace_dir = self._stretch = self.t_trace = None
        self._gc = {"t0": 0.0, "seconds": 0.0, "passes": [0, 0, 0]}

    def log(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - self.t_start:8.2f}s] {msg}",
              file=sys.stderr, flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """One part of set-up, for the split printed before the last
        line. Parts with the same name add up."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.phases[name] = self.phases.get(name, 0.0) + dt
            self.log(f"set-up {name}: {dt:.2f}s")

    # -- devices ------------------------------------------------------------
    def take_devices(self) -> None:
        cache = "off"
        if not self.rehearsal:           # a rehearsal leaves no cache behind
            from ceph_tpu.utils.compile_cache import enable_compile_cache
            cache = enable_compile_cache()
        import jax
        devs = jax.devices()
        want = int(self.cell["chips"])
        if self.rehearsal:
            if devs[0].platform == "tpu":
                raise NoDevice("a rehearsal keeps off the chip: set "
                               "JAX_PLATFORMS=cpu")
        else:
            if devs[0].platform != "tpu":
                raise NoDevice(f"no accelerator: jax sees platform "
                               f"{devs[0].platform!r}")
            if len(devs) < want:
                raise NoDevice(f"{len(devs)} chips here, the cell asks "
                               f"for {want}")
            self.peaks = peaks_mod.peaks_for(devs[0].device_kind)
        self.devices = devs[:want]
        self.log(f"devices: {len(devs)} x {devs[0].device_kind} "
                 f"({devs[0].platform}); compile cache {cache}")

    # -- the window ---------------------------------------------------------
    def _on_gc(self, phase: str, info: dict) -> None:
        """Time the interpreter's collector takes inside the window:
        an observation for the log, the collector runs as it would."""
        if phase == "start":
            self._gc["t0"] = time.perf_counter()
        else:
            self._gc["seconds"] += time.perf_counter() - self._gc["t0"]
            self._gc["passes"][info["generation"]] += 1

    def open_window(self) -> float:
        """Set-up ends here: returns the host clock at the window's
        first op."""
        self.at_open = counters.snapshot(self.osds)
        gc.callbacks.append(self._on_gc)
        self._cpu = (time.thread_time(), time.process_time())
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        return now

    def close_window(self, t_open: float) -> None:
        self.window_s = time.perf_counter() - t_open
        gc.callbacks.remove(self._on_gc)
        # CPU seconds of the thread that drives the window (the event
        # loop's, where there is one) and of the whole process: near the
        # window's length means the host's Python sets the pace
        self.obs["driver_thread_cpu_s"] = round(
            time.thread_time() - self._cpu[0], 3)
        self.obs["process_cpu_s"] = round(
            time.process_time() - self._cpu[1], 3)
        self.obs["gc_s"] = round(self._gc["seconds"], 4)
        self.obs["gc_passes"] = " ".join(map(str, self._gc["passes"]))
        self.delta = counters.delta(self.at_open,
                                    counters.snapshot(self.osds))
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak

    # -- the traced stretch -------------------------------------------------
    def trace_plan(self) -> tuple[float, float] | None:
        """(start, length) of the traced stretch in seconds from the
        window's opening, or None in an untraced run."""
        if not self.trace_on:
            return None
        plan = self.traffic.get("trace", {})
        start = float(plan.get("start_s", 2.0))
        length = float(plan.get("seconds", 5.0))
        start = min(start, max(0.0, self.seconds - length))
        return start, min(length, self.seconds - start)

    def trace_start(self) -> None:
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._stretch = jax.profiler.TraceAnnotation("bench.stretch")
        self._stretch.__enter__()
        self.t_trace = time.perf_counter()

    def trace_stop(self) -> None:
        import jax
        t1 = time.perf_counter()
        self._stretch.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.trace_span = (self.t_trace, t1)

    def reduce_trace(self) -> None:
        if self._trace_dir is None:
            return
        try:
            self.trace = trace_reduce.reduce_trace(
                self._trace_dir, span_name="bench.stretch")
        except ValueError:
            if not self.rehearsal:       # the CPU has no device plane
                raise
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    def annotate(self, name: str):
        """A host span on the trace's clock (``bench.<name>``); free
        when no trace is running."""
        if not self.trace_on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)


def _metrics_for(spec, cell_name, group):
    rows = []
    reported = {m["name"] for m in spec["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])}
    for m in spec[group]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                rows.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            rows.append(m)
    return rows


def _layer_metrics(ctx) -> dict:
    out = {}
    for m in _metrics_for(ctx.spec, ctx.cell["name"], "per_layer"):
        base, _, variant = m["name"].partition(".")
        reader = _load_py(BENCH / "layer_metrics" / f"{base}.py")
        value = reader.read(ctx, variant or None)
        if value is None:
            ctx.log(f"per-layer {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _device_block(ctx) -> dict:
    d0 = ctx.devices[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(ctx.devices),
           "memory_peak_bytes": ctx.memory_peak_bytes}
    if ctx.trace is not None:
        dev["busy_s"] = ctx.trace.busy_s("mean")
        dev["window_s"] = ctx.trace.window_ns / 1e9
    return dev


def load_cell(name: str, rehearsal: bool = False):
    """(spec, cell, configuration, traffic) of the cell of that name,
    as the files under ``configs/`` and ``traffic/`` give them; a
    rehearsal takes each file's ``rehearsal`` sizes over its own."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = _by_name(spec["workloads"], name, "workload")
    cfg_row = _by_name(spec["configs"], cell["config"], "config")
    config = json.loads((ROOT / cfg_row["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    if rehearsal:
        traffic = dict(traffic, **traffic.get("rehearsal", {}))
        config = dict(config, **config.get("rehearsal", {}))
    return spec, cell, config, traffic


def load_driver(traffic: dict):
    return _load_py(BENCH / "drivers" / f"{traffic['driver']}.py")


def run_cell(args, t_start: float) -> int:
    spec, cell, config, traffic = load_cell(args.workload, args.rehearsal)
    sys.path.insert(0, str(ROOT))
    ctx = Context(spec, cell, config, traffic, args, t_start)
    driver = load_driver(traffic)
    with ctx.phase("imports"):
        ctx.take_devices()
    ctx.log(f"cell {cell['name']} seed {ctx.seed} seconds {ctx.seconds} "
            f"trace {int(ctx.trace_on)}")
    driver.run(ctx)
    line = {"correct": ctx.compared.ok, "attempted": ctx.attempted,
            "failed": ctx.failed}
    if ctx.rehearsal:
        # a rehearsal proves the control flow; nothing it timed is a
        # device number, so none is printed
        line["rehearsal"] = True
        line["metrics"] = {}
    elif ctx.trace_on:
        line["metrics"] = _layer_metrics(ctx)
    else:
        ctx.values["setup_s"] = ctx.setup_s
        line["metrics"] = {
            m["name"]: {"value": float(ctx.values[m["name"]]),
                        "unit": m["unit"]}
            for m in _metrics_for(spec, cell["name"], "end_to_end")}
    line["device"] = _device_block(ctx)
    if ctx.trace is not None and not ctx.rehearsal:
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in ctx.trace.ops],
            "idle_gaps": [[n, s] for n, s in ctx.trace.gaps]}
    line["compared"] = ctx.compared.rows
    split = {k: round(v, 3) for k, v in ctx.phases.items()}
    ctx.log("set-up split " + json.dumps(split))
    ctx.log("counter deltas " + json.dumps(
        {k: v for k, v in sorted(ctx.delta.items()) if v}))
    ctx.log("window " + json.dumps(
        {k: v for k, v in ctx.obs.items()
         if isinstance(v, (int, float, str))}))
    for name, row in ctx.compared.rows.items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU (JAX_PLATFORMS=cpu): "
                         "control flow only, prints no metric")
    args = ap.parse_args(argv)
    try:
        return run_cell(args, t_start)
    except NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3

"""Device-runtime observability: the monitor every jit entry point
reports through (round 14).

The device runtime is a first-class observed subsystem now, the same
way round 8 made ops and round 12 made counters observable. Three
blind spots motivated it:

- **silent kernel-path degradation**: a daemon that loses its fused
  Pallas plan serves CRUSH ~34x slower with zero signal — until now
  the only detector was a bench run's ``path_expected_vs_actual`` row
  (round 10), which a production daemon never executes;
- **invisible jit compiles**: a recompile (shape instability, plan
  rebuild) stalls the shared event loop for seconds — round 12 had to
  stall-clamp mgr liveness around exactly this without ever being able
  to SEE the compile that caused it;
- **unaccounted transfers**: H2D staging and D2H readbacks dominate
  wall time when the host link is slow, and nothing counted the bytes.

Two kinds of :class:`DeviceRuntimeMonitor` exist:

- the **process singleton** (``devmon()``, counter family
  ``device_runtime``, registered in the process collection): the
  compile/transfer side. Process-level code — ``crush.mapper``,
  ``crush.sharded_sweep``, ``ec.jax_plugin`` — reports here, because
  the jit caches it observes are process-wide. It also listens to
  ``jax.monitoring`` (registered once, with the singleton): every
  backend compile is counted (``xla_compiles``, ``xla_compile_seconds``,
  ``xla_cache_hits`` for those the persistent cache served) and kept in
  a bounded list (:func:`compile_events`) beside the ``jit_call`` it
  happened under. ``jit_compiles`` stays what it was — first calls per
  (function object, shape), re-traces that hit JAX's caches among them.
  A daemon's Tracer can be attached (:meth:`attach_tracer`) so each
  ``jit_call`` that really compiled emits one ``jit_compile`` span
  (never sampled away — compiles are rare, operator-critical events;
  its duration is the backend's, its ``cached`` tag says whether the
  persistent cache served it) that ships monward on the daemon's
  existing report piggyback and lands in ``trace ls/show``.
- **per-daemon instances** (``register=False``, counter family
  ``devmon``, reaching ``/metrics`` only through the daemon's
  MMgrReport session — the round-13 ``osd_ec_agg`` discipline): the
  kernel-path health side. Every ``Mapper``/``OSDMapMapping`` sweep
  site records which engine actually ran (:meth:`record_launch`) and
  whether it matched the expectation (:meth:`record_path_check`):
  ``devmon_expected_engine`` pins the operator's deployed expectation
  ("this daemon runs pallas"), ``auto`` trusts the built plan so the
  only mismatch is a plan that silently degraded mid-run.

Cluster surfacing: counters flow through the existing
MgrReporter -> DaemonStateIndex -> prometheus leg as dedicated
``ceph_device_*`` rows; the cumulative (checks, mismatches, compiles,
transfer bytes) piggyback monward on MPGStats (``device_health``), the
mon debounces per-report mismatch rates into the
**KERNEL_PATH_DEGRADED** health check (``mon_kernel_path_*`` knobs,
same confirm/clear discipline as OSD_SLOW), and
``ceph device-runtime status`` serves the per-daemon table.
"""

from __future__ import annotations

import threading
import time

from ceph_tpu.utils.perf_counters import PerfCountersBuilder

# engines a path string can resolve to ("+sharded" is a suffix, not an
# engine: the sharded sweep serves whichever engine the single-device
# path would)
ENGINES = ("pallas", "xla", "scalar")

# warm-set bound: (fn, key) pairs tracked for first-call compile
# detection. Shape churn past this evicts the OLDEST entry only, so a
# long-running daemon's hot paths stay warm (a full clear would
# re-count every hot path's next call as a fresh compile).
_WARM_MAX = 4096

# device fault injection (round 16): jit_call is the one chokepoint
# every jit-backed device call passes through, so it is also where
# sim.faults' device kinds (jit_fail / jit_stall / bad_result) fire.
# Installed process-wide by Cluster.install_faults; None in production.
_fault_injector = None

# -- the compile listener ------------------------------------------------------
# jax.monitoring events: the backend compile (fun_name, seconds — a
# retrieval from the persistent cache reports here too, with the
# retrieval's time) and the marker that precedes it on a cache hit
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_EVENTS_MAX = 4096
# (perf_counter_ns at the compile's end, fun_name, seconds, cached,
# program): program is the jit_call in progress on that thread, else
# "other"
_events: list[tuple] = []
_tls = threading.local()


def compile_events() -> list[tuple]:
    """Every backend compile this process has seen since the singleton
    was made (the oldest go once the list is full)."""
    return _events


def _on_event(event: str, **_kw) -> None:
    if event == _EV_CACHE_HIT:
        _tls.hit = True


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event != _EV_COMPILE or _singleton is None:
        return
    cached = bool(getattr(_tls, "hit", False))
    _tls.hit = False
    calls = getattr(_tls, "calls", None)
    seconds = float(seconds)
    ev = (time.perf_counter_ns(), str(kw.get("fun_name", "?")), seconds,
          cached, calls[-1][0] if calls else "other")
    if calls:
        calls[-1][1].append(ev)
    if len(_events) >= _EVENTS_MAX:
        del _events[:_EVENTS_MAX // 4]
    _events.append(ev)
    perf = _singleton.perf
    perf.inc("xla_compiles")
    perf.tinc("xla_compile_seconds", seconds)
    if cached:
        perf.inc("xla_cache_hits")


def _listen() -> None:
    from jax import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def set_fault_injector(inj) -> None:
    """Attach (or detach, with None) the process's FaultInjector to
    the jit_call chokepoint. The injector is consulted only when it
    has device rules installed — the no-faults fast path costs one
    attribute read."""
    global _fault_injector
    _fault_injector = inj


def _corrupt_result(out):
    """The ``bad_result`` fault: flip one element of the returned
    array (first element of a tuple result — the payload; EC's crc
    sidecar rides along untouched so checksum verification still
    sees the corrupt payload). Returns a host copy; shapes/dtypes
    are preserved so only bit-exact checks can tell."""
    import numpy as np
    if isinstance(out, tuple):
        if not out:
            return out
        return (_corrupt_result(out[0]),) + tuple(out[1:])
    try:
        arr = np.array(out)
    except Exception:
        return out
    if arr.size == 0:
        return out
    flat = arr.reshape(-1)
    if arr.dtype.kind in "iu":
        flat[0] ^= 1
    elif arr.dtype.kind == "f":
        flat[0] = flat[0] + 1.0
    else:
        return out
    return arr


def normalize_engine(path: str | None) -> str:
    """Collapse a mapping path to its base engine:
    'pallas-interpret' -> 'pallas', 'xla+sharded' -> 'xla'."""
    if not path:
        return "?"
    base = path.split("+", 1)[0]
    if base.startswith("pallas"):
        return "pallas"
    return base if base in ENGINES else "?"


class DeviceRuntimeMonitor:
    """Compile accounting + kernel-path health + transfer gauges.

    ``register=True`` puts the counter family in the process-wide
    collection (the ``devmon()`` singleton); per-daemon instances pass
    ``register=False`` and reach `/metrics` only through their report
    session. ``config`` is the owning daemon's LIVE config dict —
    ``devmon_expected_engine`` is read per check, so a runtime flip
    applies to the next sweep."""

    def __init__(self, name: str = "device_runtime",
                 register: bool = True,
                 config: dict | None = None):
        self.config = config if config is not None else {}
        self.perf = (
            PerfCountersBuilder(name)
            .add_u64_counter("jit_compiles",
                             "first-call jit compiles observed (per "
                             "distinct function + abstract shape key)")
            .add_time("jit_compile_seconds",
                      "wall seconds spent in compile-triggering first "
                      "calls")
            .add_u64_counter("xla_compiles",
                             "backend compiles jax reported (process "
                             "monitor only; persistent-cache retrievals "
                             "included)")
            .add_time("xla_compile_seconds",
                      "seconds inside those backend compiles")
            .add_u64_counter("xla_cache_hits",
                             "backend compiles the persistent compile "
                             "cache served")
            .add_u64_counter("launches_pallas",
                             "map/sweep launches served by the fused "
                             "Pallas kernel (interpret included)")
            .add_u64_counter("launches_xla",
                             "map/sweep launches served by the XLA "
                             "rule VM")
            .add_u64_counter("launches_scalar",
                             "map/sweep launches served by the scalar "
                             "spec walk (legacy tunables)")
            .add_u64_counter("launches_sharded",
                             "launches that rode the mesh-sharded "
                             "path (counted in addition to the engine)")
            .add_u64_counter("path_checks",
                             "expected-vs-actual engine checks at "
                             "Mapper/OSDMapMapping sweep sites")
            .add_u64_counter("path_mismatch",
                             "sweeps whose actual engine differed "
                             "from the expected one (the silent-"
                             "degradation signal)")
            .add_u64_counter("h2d_bytes",
                             "host->device bytes staged (mapper "
                             "packing, EC pipeline ingest)")
            .add_u64_counter("d2h_bytes",
                             "device->host bytes read back")
            .add_u64("device_bytes_staged",
                     "bytes of the most recent staging op (gauge)")
            .add_u64("device_bytes_watermark",
                     "largest single staging op seen (gauge, "
                     "monotone max)")
            .add_u64_counter("quarantine_entries",
                             "kernel-path quarantine entries (a device "
                             "failure benched the fused kernel)")
            .add_u64_counter("quarantine_exits",
                             "kernel-path re-promotions (a bit-exact "
                             "probe passed and the kernel serves again)")
            .add_u64_counter("quarantine_probes",
                             "backoff re-probe attempts against a "
                             "quarantined kernel")
            .add_u64_counter("quarantine_probe_failures",
                             "re-probes that raised or mismatched the "
                             "serving path bit-exactly")
            .add_u64("quarantined_now",
                     "kernels currently quarantined (serving the "
                     "fallback engine, re-probe pending; gauge)")
            .add_u64("reprobing_now",
                     "quarantined kernels past their first failed "
                     "re-probe (gauge)")
            .add_u64("quarantine_permanent_now",
                     "kernels permanently disabled after "
                     "crush_kernel_reprobe_disable_after consecutive "
                     "failures (gauge)")
            .add_u64_counter("faults_injected",
                             "device faults fired at the jit_call "
                             "chokepoint (sim.faults device kinds)")
            .add_u64_counter("stream_fallbacks",
                             "streaming-encode pipelines that fell "
                             "back to the unpipelined path")
            .create_perf_counters(register=register))
        self.tracer = None           # utils.tracing.Tracer | None
        self._lock = threading.Lock()
        # insertion-ordered: eviction at _WARM_MAX pops oldest only
        self._warm: dict[tuple, None] = {}
        # fn name -> {count, seconds, last_key, last_seconds}
        self.functions: dict[str, dict] = {}
        self._watermark = 0
        self.last_mismatch: dict | None = None
        # quarantine token -> "quarantined"|"reprobing"|"permanent"
        self._quarantine: dict = {}

    # -- wiring ------------------------------------------------------------
    def attach_tracer(self, tracer) -> None:
        """Attach the owning daemon's Tracer: every ``jit_call`` under
        which the backend compiled emits one ``jit_compile`` span
        through it (in multi-daemon test processes the last attach
        wins — one span per compiling call either way, never zero,
        never double). The span has to ship monward on SOME daemon's
        report, so a tracer it is."""
        self.tracer = tracer

    # -- compile accounting ------------------------------------------------
    def jit_call(self, fn_name: str, key, fn, *args):
        """Run ``fn(*args)``, recording the call as a jit compile when
        this (fn_name, key) pair has never run before. ``key`` must
        capture the jit cache identity — callers pass (id(jitted_fn),
        abstract shape), so a process-shared lru'd program is warm
        across Mapper instances while a per-Mapper kernel wrapper is
        cold once per Mapper. Warm calls cost one set lookup; a failed
        first call un-warms so the retry path's compile still counts.

        This is also the device-fault injection chokepoint: when a
        FaultInjector with device rules is attached
        (:func:`set_fault_injector`), its verdict runs first —
        ``jit_stall`` sleeps here, ``jit_fail`` raises before any
        warm-set bookkeeping (so a later retry still counts its
        compile), ``bad_result`` corrupts the completed result."""
        corrupt = False
        inj = _fault_injector
        if inj is not None and inj.has_device_rules():
            stall, fail, corrupt = inj.device_verdicts(
                fn_name, str(key))
            if stall > 0:
                time.sleep(stall)
            if fail:
                self.perf.inc("faults_injected")
                raise RuntimeError(
                    f"injected device fault: jit_fail on {fn_name}")
        k = (fn_name, key)
        with self._lock:
            warm = k in self._warm
            if not warm:
                if len(self._warm) >= _WARM_MAX:
                    self._warm.pop(next(iter(self._warm)))
                self._warm[k] = None
        # the compile listener files what the backend compiles under
        # this call (warm by our book or not: the book can be wrong)
        calls = getattr(_tls, "calls", None)
        if calls is None:
            calls = _tls.calls = []
        mine = (fn_name, [])
        calls.append(mine)
        t0 = 0.0 if warm else time.perf_counter()
        try:
            out = fn(*args)
        except BaseException:
            if not warm:
                with self._lock:
                    self._warm.pop(k, None)
            raise
        finally:
            calls.pop()
        if not warm:
            self.record_compile(fn_name, key, time.perf_counter() - t0)
        if mine[1]:
            self._emit_compile_span(fn_name, key, mine[1])
        if corrupt:
            self.perf.inc("faults_injected")
            out = _corrupt_result(out)
        return out

    def record_compile(self, fn_name: str, key, seconds: float) -> None:
        """One observed FIRST CALL: counter + time sum + per-function
        table. Its wall is the call's, whatever JAX's caches did."""
        seconds = max(float(seconds), 0.0)
        self.perf.inc("jit_compiles")
        self.perf.tinc("jit_compile_seconds", seconds)
        with self._lock:
            ent = self.functions.setdefault(
                fn_name, {"count": 0, "seconds": 0.0})
            ent["count"] += 1
            ent["seconds"] = round(ent["seconds"] + seconds, 6)
            ent["last_key"] = str(key)[:120]
            ent["last_seconds"] = round(seconds, 6)

    def _emit_compile_span(self, fn_name: str, key, events) -> None:
        """One ``jit_compile`` span for a call under which the backend
        compiled: the trace id is minted directly (head sampling must
        not drop compile evidence), the duration is the backend's own
        seconds added up, and it ends where the last compile ended."""
        tracer = self.tracer
        if tracer is None:
            return
        from ceph_tpu.utils.tracing import Span, new_trace_id
        seconds = sum(ev[2] for ev in events)
        s = Span(tracer, "jit_compile", new_trace_id(),
                 tags={"fn": fn_name, "key": str(key)[:120],
                       "cached": all(ev[3] for ev in events),
                       "xla": [ev[1] for ev in events][:8]})
        s.close_at(events[-1][0] - int(seconds * 1e9), events[-1][0])

    # -- kernel-path health ------------------------------------------------
    def expected_engine(self, plan_path: str | None) -> str:
        """The engine this monitor's owner EXPECTS sweeps to run on:
        the ``devmon_expected_engine`` knob when pinned, else the
        plan's own prediction (``plan_path``) — under which the only
        possible mismatch is a plan that degraded mid-run."""
        want = str(self.config.get("devmon_expected_engine", "auto"))
        if want in ("", "auto"):
            return normalize_engine(plan_path)
        return want

    def record_launch(self, path: str | None, n: int = 1) -> None:
        """Count a map/sweep launch by the engine that actually ran."""
        eng = normalize_engine(path)
        if eng in ENGINES:
            self.perf.inc(f"launches_{eng}", n)
        if path and "+sharded" in path:
            self.perf.inc("launches_sharded", n)

    def record_path_check(self, expected: str | None,
                          actual: str | None) -> bool:
        """One expected-vs-actual engine check; returns True on
        mismatch. ``expected`` may be a raw path or a bare engine;
        both sides normalize, so 'pallas-interpret' == 'pallas' and
        the '+sharded' suffix never trips a false mismatch."""
        e, a = normalize_engine(expected), normalize_engine(actual)
        self.perf.inc("path_checks")
        if e == a or e == "?":
            return False
        self.perf.inc("path_mismatch")
        self.last_mismatch = {"expected": e, "actual": a,
                              "stamp": time.time()}
        return True

    def record_sweep(self, plan_path: str | None, actual: str | None,
                     n_launches: int = 1) -> bool:
        """The per-sweep-site combo: launch counter + expectation
        check (knob-pinned or plan-trusted)."""
        self.record_launch(actual, n_launches)
        return self.record_path_check(
            self.expected_engine(plan_path), actual)

    # -- kernel quarantine (round 16) --------------------------------------
    def set_quarantine_state(self, token, state: str | None) -> None:
        """Track one kernel owner's quarantine state (keyed by an
        opaque token — Mappers use their per-incarnation devmon
        token). ``None`` clears. The three gauges always reflect the
        live table."""
        with self._lock:
            if state is None:
                self._quarantine.pop(token, None)
            else:
                self._quarantine[token] = state
            states = list(self._quarantine.values())
        self.perf.set("quarantined_now",
                      sum(1 for s in states
                          if s in ("quarantined", "reprobing")))
        self.perf.set("reprobing_now",
                      sum(1 for s in states if s == "reprobing"))
        self.perf.set("quarantine_permanent_now",
                      sum(1 for s in states if s == "permanent"))

    def record_quarantine_enter(self, token,
                                state: str = "quarantined") -> None:
        self.perf.inc("quarantine_entries")
        self.set_quarantine_state(token, state)

    def record_quarantine_exit(self, token) -> None:
        self.perf.inc("quarantine_exits")
        self.set_quarantine_state(token, None)

    def record_probe(self, ok: bool) -> None:
        self.perf.inc("quarantine_probes")
        if not ok:
            self.perf.inc("quarantine_probe_failures")

    # -- transfers / memory ------------------------------------------------
    def record_h2d(self, nbytes: int) -> None:
        if nbytes > 0:
            self.perf.inc("h2d_bytes", int(nbytes))

    def record_d2h(self, nbytes: int) -> None:
        if nbytes > 0:
            self.perf.inc("d2h_bytes", int(nbytes))

    def note_staging(self, nbytes: int) -> None:
        """One staging op's device-resident footprint: the gauge holds
        the most recent op, the watermark the largest ever (per-op
        max, NOT a running sum — frees are not tracked, and a
        cumulative gauge would be a lie)."""
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        self.perf.set("device_bytes_staged", nbytes)
        with self._lock:
            if nbytes > self._watermark:
                self._watermark = nbytes
        self.perf.set("device_bytes_watermark", self._watermark)

    # -- views -------------------------------------------------------------
    def mismatch_ratio(self) -> float:
        d = self.perf.dump()
        checks = int(d.get("path_checks", 0))
        return (int(d.get("path_mismatch", 0)) / checks) if checks \
            else 0.0

    def health_report(self) -> dict[str, int]:
        """The MPGStats ``device_health`` piggyback payload: this
        monitor's cumulative path health merged with the process
        singleton's compile/transfer side (one daemon per process in
        production, so the merge IS the daemon's view). All u64."""
        d = self.perf.dump()
        proc = self if self is _singleton else devmon()
        p = proc.perf.dump() if proc is not self else d
        return {
            "checks": int(d.get("path_checks", 0)),
            "mismatches": int(d.get("path_mismatch", 0)),
            "launches_pallas": int(d.get("launches_pallas", 0)),
            "launches_xla": int(d.get("launches_xla", 0)),
            "launches_scalar": int(d.get("launches_scalar", 0)),
            "launches_sharded": int(d.get("launches_sharded", 0)),
            "compiles": int(p.get("jit_compiles", 0)),
            "compile_ms": int(
                float(p.get("jit_compile_seconds", 0.0)) * 1e3),
            "h2d_bytes": int(p.get("h2d_bytes", 0)),
            "d2h_bytes": int(p.get("d2h_bytes", 0)),
            # quarantine lives process-side (Mappers are process-level)
            "quarantined": int(p.get("quarantined_now", 0)),
            "reprobing": int(p.get("reprobing_now", 0)),
            "quarantine_permanent": int(
                p.get("quarantine_permanent_now", 0)),
            "quarantine_entries": int(p.get("quarantine_entries", 0)),
            "quarantine_exits": int(p.get("quarantine_exits", 0)),
        }

    def dump(self) -> dict:
        """The asok ``device`` block / ``device-runtime status``
        payload for this monitor."""
        import jax
        out = {
            "engine": jax.default_backend(),
            "expected_engine": str(
                self.config.get("devmon_expected_engine", "auto")),
            "counters": self.perf.dump(),
            "mismatch_ratio": round(self.mismatch_ratio(), 4),
        }
        if self.last_mismatch:
            out["last_mismatch"] = dict(self.last_mismatch)
        with self._lock:
            if self.functions:
                out["compiles_by_fn"] = {
                    k: dict(v) for k, v in sorted(self.functions.items())}
        return out


_singleton: DeviceRuntimeMonitor | None = None


def engine_name() -> str:
    """The process's default jax backend ('cpu'/'tpu'/...) — the
    `device_engine` field daemons stamp on their reports."""
    import jax
    return str(jax.default_backend())


def devmon() -> DeviceRuntimeMonitor:
    """The process singleton (counter family ``device_runtime``) every
    process-level jit entry point reports through."""
    global _singleton
    if _singleton is None:
        _singleton = DeviceRuntimeMonitor()
        _listen()
    return _singleton

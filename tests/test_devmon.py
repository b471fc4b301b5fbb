"""Device-runtime observability plane (round 14).

Acceptance surface:

- one COLD ``Mapper`` compile produces exactly one ``jit_compile``
  span (duration inside the client-observed stall) and exactly one
  compile-counter increment — and a warm call produces neither;
- ``Mapper`` path recording is PER CALL (``map_pgs_path`` returns
  this call's engine) with ``last_map_path`` surviving only as a
  best-effort mirror — the single-slot race two interleaved sweeps
  could hit is pinned here;
- a cluster whose daemons are knob-pinned to expect the Pallas engine
  (``devmon_expected_engine=pallas``) while actually serving XLA sees
  the mismatch counter in `/metrics`
  (``ceph_device_path_mismatch_total``, built from REPORTED state),
  trips KERNEL_PATH_DEGRADED after the ``mon_kernel_path_confirm``
  debounce, and clears on heal (knob back to ``auto``);
- a watched daemon task dying with a real exception ships a bounded
  MCrashReport: `ceph crash ls/info` serve it, RECENT_CRASH warns,
  and `ceph crash archive` clears the warning.

Budget discipline: ONE vstart cluster carries every cluster assert
(mismatch counters, /metrics row, health trip + heal, CLI verbs,
crash capture); everything else is unit-level.
"""

import asyncio
import json
import time

import numpy as np
import pytest

from ceph_tpu.crush import builder
from ceph_tpu.crush.builder import TYPE_HOST
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.utils.devmon import (
    DeviceRuntimeMonitor, devmon, normalize_engine,
)
from ceph_tpu.utils.tracing import Tracer


def run(coro):
    asyncio.run(coro)


def _two_rule_map(n_osds: int = 64):
    """rule 0: replicated chooseleaf firstn (kernel-eligible);
    rule 1: chooseleaf indep (EC shape — NOT kernel-eligible), so the
    two rules resolve to different engines under interpret mode."""
    osds_per_host = 8
    m, root = builder.build_hierarchy(n_osds // osds_per_host,
                                      osds_per_host, n_racks=2)
    builder.add_simple_rule(m, root, TYPE_HOST)
    builder.add_simple_rule(m, root, TYPE_HOST, indep=True)
    return m


# -- units: the monitor itself ----------------------------------------------

def test_normalize_engine():
    assert normalize_engine("pallas") == "pallas"
    assert normalize_engine("pallas-interpret") == "pallas"
    assert normalize_engine("pallas+sharded") == "pallas"
    assert normalize_engine("xla+sharded") == "xla"
    assert normalize_engine("scalar") == "scalar"
    assert normalize_engine(None) == "?"
    assert normalize_engine("florp") == "?"


def test_record_sweep_knob_vs_plan():
    """`devmon_expected_engine` pins the deployment contract (read
    LIVE); 'auto' trusts the plan, so the only mismatch then is a
    degrade relative to the plan's own prediction."""
    cfg = {"devmon_expected_engine": "pallas"}
    dm = DeviceRuntimeMonitor(name="devmon_unit0", register=False,
                              config=cfg)
    # pinned pallas, actually xla: mismatch
    assert dm.record_sweep("xla", "xla") is True
    # pinned pallas, actually the interpreted kernel: NOT a mismatch
    assert dm.record_sweep("pallas-interpret",
                           "pallas-interpret+sharded") is False
    d = dm.perf.dump()
    assert d["path_checks"] == 2 and d["path_mismatch"] == 1
    assert d["launches_xla"] == 1 and d["launches_pallas"] == 1
    assert d["launches_sharded"] == 1
    assert dm.last_mismatch["expected"] == "pallas"
    assert dm.last_mismatch["actual"] == "xla"
    # live flip to auto: plan-trusted, same-engine sweeps are clean
    cfg["devmon_expected_engine"] = "auto"
    assert dm.record_sweep("xla", "xla") is False
    # ... and a mid-run degrade (plan pallas -> actual xla) still trips
    assert dm.record_sweep("pallas", "xla") is True
    assert dm.mismatch_ratio() == pytest.approx(2 / 4)
    hr = dm.health_report()
    assert hr["checks"] == 4 and hr["mismatches"] == 2
    # the merged process side carries compile/transfer keys (all u64)
    for key in ("compiles", "compile_ms", "h2d_bytes", "d2h_bytes"):
        assert isinstance(hr[key], int), key


def test_jit_call_warm_and_failure_unwarm():
    dm = DeviceRuntimeMonitor(name="devmon_unit1", register=False)
    calls = []

    def fn(x):
        calls.append(x)
        if x == "boom":
            raise ValueError("boom")
        return x

    assert dm.jit_call("f", (1,), fn, "a") == "a"
    assert dm.perf.dump()["jit_compiles"] == 1
    # warm: same key, no second compile
    assert dm.jit_call("f", (1,), fn, "b") == "b"
    assert dm.perf.dump()["jit_compiles"] == 1
    # a failed FIRST call un-warms so the retry's compile counts
    with pytest.raises(ValueError):
        dm.jit_call("g", (2,), fn, "boom")
    assert dm.perf.dump()["jit_compiles"] == 1
    assert dm.jit_call("g", (2,), fn, "ok") == "ok"
    assert dm.perf.dump()["jit_compiles"] == 2
    assert dm.functions["f"]["count"] == 1


# -- acceptance: one cold compile -> one span + one counter ------------------

def test_cold_mapper_compile_one_span_one_increment():
    """The acceptance pin: a cold Mapper compile produces exactly ONE
    `jit_compile` span whose duration sits inside the client-observed
    stall, and exactly one compile-counter increment; the warm call
    adds neither."""
    dm = devmon()
    tracer = Tracer("devmon-unit", {"trace_slow_keep_s": 0.0})
    old_tracer = dm.tracer
    dm.attach_tracer(tracer)
    try:
        m = Mapper(_two_rule_map(56), block=1 << 10)
        xs = np.arange(37, dtype=np.uint32)     # odd width: cold key
        before = dm.perf.dump()["jit_compiles"]

        t0 = time.perf_counter()
        out, path = m.map_pgs_path(0, xs, 3)
        stall = time.perf_counter() - t0

        after = dm.perf.dump()["jit_compiles"]
        assert after - before == 1, (before, after)
        spans = [s for s in tracer.dump()["spans"]
                 if s["name"] == "jit_compile"]
        assert len(spans) == 1, spans
        assert 0.0 < spans[0]["duration"] <= stall
        assert spans[0]["tags"]["fn"] == "crush_map_pgs"
        # compile evidence ships monward on the daemon piggyback
        assert tracer.ship_pending() >= 1
        assert out.shape == (37, 3)

        # warm call: no double count, no second span
        m.map_pgs(0, xs, 3)
        assert dm.perf.dump()["jit_compiles"] == after
        assert len([s for s in tracer.dump()["spans"]
                    if s["name"] == "jit_compile"]) == 1
    finally:
        dm.attach_tracer(old_tracer)


# -- the per-call path fix (the last_map_path single-slot race) --------------

def test_map_pgs_path_is_per_call():
    """Two interleaved calls on ONE Mapper that serve different paths
    (the mesh route kicks in per call by batch width) each get THEIR
    OWN path back; the `last_map_path` attribute is last-writer-wins
    — exactly the single-slot race the per-call return exists to fix.
    (Budget note: reuses the cold test's map shape so the rule-VM
    compile is warm; the Pallas-interpret variant of this pin costs
    minutes of interpret-mode compile and is deliberately avoided.)"""
    from ceph_tpu.parallel import local_mesh
    m = Mapper(_two_rule_map(56), block=1 << 10,
               mesh=local_mesh(), mesh_min_batch=64)
    xs_small = np.arange(37, dtype=np.uint32)   # < mesh_min_batch
    xs_big = np.arange(128, dtype=np.uint32)    # >= mesh_min_batch
    out_b, pb = m.map_pgs_path(0, xs_big, 3)
    assert pb == "xla+sharded", pb
    assert out_b.shape == (128, 3)
    out_s, ps = m.map_pgs_path(0, xs_small, 3)
    assert ps == "xla", ps
    # the mirror now shows the LAST call's engine — the singleton
    # slot cannot answer "which path ran MY sweep"...
    assert m.last_map_path == "xla"
    # ...but the per-call value still can
    _, pb2 = m.map_pgs_path(0, xs_big, 3)
    assert pb2 == "xla+sharded"
    assert m.last_map_path == "xla+sharded"
    # sweep_path carries the same per-call contract (small sweep:
    # the plain single-device path)
    counts, bad, sp = m.sweep_path(0, 0, 32, 3)
    assert sp == "xla"
    assert int(np.asarray(counts).sum()) == 32 * 3


def test_kernel_jit_key_carries_variant_tag():
    """Round 15: the compile-warmth key of a kernel-path jit wrapper
    carries the kernel-variant tag, so a `jit_compile` span (its key
    tag is str(key)) distinguishes a fresh candidate-batched-kernel
    compile from a stale plan's re-trace; XLA keys stay variant-free
    (the rule VM did not restructure)."""
    from ceph_tpu.crush import pallas_mapper as pm
    m = Mapper(_two_rule_map(56), block=1 << 10)
    kkey = m._jit_key(0, 3, True, 64)
    assert pm.KERNEL_VARIANT in kkey, kkey
    assert pm.KERNEL_VARIANT not in m._jit_key(0, 3, False, 64)
    # two Mapper incarnations over one map still key apart (the
    # per-incarnation token survives beside the variant tag)
    m2 = Mapper(_two_rule_map(56), block=1 << 10)
    assert m2._jit_key(0, 3, True, 64) != kkey


def test_degraded_mapper_keeps_counting_mismatches():
    """A Mapper whose fused kernel failed mid-run stays pinned to the
    engine it PROMISED ('pallas') under devmon_expected_engine=auto:
    every later sweep keeps counting a mismatch — the baseline must
    not silently re-heal to the fallback engine (the 34x-slower
    silent-degradation case the plane exists to catch)."""
    dm = devmon()
    # reprobe pinned far out: this test is about the PINNED baseline,
    # not the round-16 re-probe cycle (covered below) — a default
    # 0.5s backoff could fire a probe mid-test on a slow host
    m = Mapper(_two_rule_map(56), block=1 << 10,
               config={"crush_kernel_reprobe_base": 3600.0})
    xs = np.arange(37, dtype=np.uint32)     # warm shape (cold test)
    assert m.expected_path(0, 3) == "xla"
    before = dm.perf.dump()["path_mismatch"]
    m.map_pgs(0, xs, 3)                     # healthy: no mismatch
    assert dm.perf.dump()["path_mismatch"] == before
    # simulate the kernel-failure degrade discipline
    m._disable_kernel("unit", RuntimeError("injected"))
    assert m.expected_path(0, 3) == "pallas"
    m.map_pgs(0, xs, 3)
    m.map_pgs(0, xs, 3)
    assert dm.perf.dump()["path_mismatch"] == before + 2
    # hygiene: drop this mapper's quarantine token so later tests see
    # clean gauges (the token table is process-global)
    dm.set_quarantine_state(m._devmon_token, None)


# -- round 16: warm-set eviction, fault injection, kernel quarantine --------

def test_warm_set_evicts_oldest_only(monkeypatch):
    """At _WARM_MAX the warm set evicts the OLDEST key only — the
    pre-round-16 full clear made every concurrently-live jit look
    cold again on its next call, spiking jit_compiles (and minting
    phantom compile spans) across the board."""
    from ceph_tpu.utils import devmon as devmon_mod
    monkeypatch.setattr(devmon_mod, "_WARM_MAX", 3)
    dm = DeviceRuntimeMonitor(name="devmon_unit_warm", register=False)
    for i in range(3):
        dm.jit_call("f", (i,), lambda: i)
    assert dm.perf.dump()["jit_compiles"] == 3
    # 4th distinct key evicts ONLY ("f", (0,))
    dm.jit_call("f", (3,), lambda: 3)
    assert dm.perf.dump()["jit_compiles"] == 4
    # keys 1..3 are still warm: no new compiles
    for i in (1, 2, 3):
        dm.jit_call("f", (i,), lambda: i)
    assert dm.perf.dump()["jit_compiles"] == 4
    # the evicted oldest re-counts (evicting ("f",(1,)) in turn)
    dm.jit_call("f", (0,), lambda: 0)
    assert dm.perf.dump()["jit_compiles"] == 5


def test_device_fault_injection_at_jit_call():
    """The devmon chokepoint honors device FaultRules: jit_fail
    raises before warm bookkeeping (the retry's compile still
    counts), bad_result corrupts the completed array, count bounds a
    rule to its first N firings, and key patterns target by jit-key
    string."""
    from ceph_tpu.sim import faults as F
    from ceph_tpu.utils import devmon as devmon_mod
    inj = F.FaultInjector(seed=3)
    inj.install("dev", [
        F.jit_fail("ec_encode", count=1),
        F.bad_result("crush_map_pgs", key="*'kern'*", count=1),
    ])
    dm = DeviceRuntimeMonitor(name="devmon_unit_fi", register=False)
    devmon_mod.set_fault_injector(inj)
    try:
        # fn-name pattern: only ec_encode fails, and only once
        with pytest.raises(RuntimeError, match="injected device"):
            dm.jit_call("ec_encode", ("xla", 1), lambda: "never")
        assert dm.jit_call("ec_encode", ("xla", 1), lambda: "ok") \
            == "ok"
        # the failed first call un-warmed: the retry counted a compile
        assert dm.perf.dump()["jit_compiles"] == 1
        # key pattern: the xla-keyed call passes clean...
        clean = dm.jit_call("crush_map_pgs", ("xla", 4),
                            lambda: np.arange(6))
        assert np.array_equal(clean, np.arange(6))
        # ...the kern-keyed call is corrupted (one element flipped)
        bad = dm.jit_call("crush_map_pgs", ("kern", "v", 4),
                          lambda: np.arange(6))
        assert bad.shape == (6,) and \
            not np.array_equal(bad, np.arange(6))
        assert int((bad != np.arange(6)).sum()) == 1
        # count exhausted: clean again
        ok = dm.jit_call("crush_map_pgs", ("kern", "v", 4),
                         lambda: np.arange(6))
        assert np.array_equal(ok, np.arange(6))
        assert dm.perf.dump()["faults_injected"] == 2
    finally:
        devmon_mod.set_fault_injector(None)


def _quarantine_mapper(fake_kernel, **knobs):
    """A Mapper whose 'kernel' is a stand-in jax fn — the quarantine
    state machine is exercised without paying interpret-mode compiles
    (the REAL kernel cycle runs in the device_storm acceptance and in
    test_pallas_mapper's interpret suite)."""
    cfg = {"crush_kernel_reprobe_base": 0.0,
           "crush_kernel_reprobe_max": 0.0,
           "crush_kernel_reprobe_disable_after": 3}
    cfg.update(knobs)
    m = Mapper(_two_rule_map(56), block=1 << 10, config=cfg)
    fn = fake_kernel(m)
    # gate on _kernel_mode like the real body: while quarantined
    # (mode None) the serving path must see NO kernel and ride XLA
    m._kernel_body = lambda ruleno, result_max: (
        fn if m._kernel_mode is not None else None)
    m._kernel_mode = "interpret"
    return m


def test_kernel_quarantine_reprobe_cycle():
    """fail -> quarantined (XLA serves the SAME call) -> the due
    probe runs the kernel on a sample, matches the serving path
    bit-exact, and RE-PROMOTES: expected_path returns to pallas, the
    serving output is unchanged, and the devmon records the full
    enter/probe/exit cycle."""
    dm = devmon()
    before = dm.perf.dump()
    # the stand-in kernel IS the serving rule fn: bit-exact trivially
    m = _quarantine_mapper(lambda m: m._rule_fn(0, 3))
    xs = np.arange(37, dtype=np.uint32)
    ref = np.asarray(m.map_pgs(0, xs, 3))

    m._disable_kernel("unit", RuntimeError("injected"))
    info = m.kernel_quarantine_info()
    assert info == {"state": "quarantined", "failures": 1,
                    "next_probe_in_s": 0.0}
    assert m.expected_path(0, 3) == "pallas"   # the promise holds
    # base=0: the next fresh call probes, passes, and re-promotes
    out, path = m.map_pgs_path(0, xs, 3)
    assert m.kernel_quarantine_info() is None
    assert path == "pallas-interpret", path
    assert np.array_equal(np.asarray(out), ref)
    after = dm.perf.dump()
    assert after["quarantine_entries"] - \
        before.get("quarantine_entries", 0) == 1
    assert after["quarantine_exits"] - \
        before.get("quarantine_exits", 0) == 1
    assert after["quarantine_probes"] - \
        before.get("quarantine_probes", 0) == 1
    assert after["quarantine_probe_failures"] == \
        before.get("quarantine_probe_failures", 0)
    # this mapper's enter/exit netted zero on the live gauge
    assert after["quarantined_now"] == before.get("quarantined_now", 0)


def test_kernel_quarantine_permanent_after_disable_after():
    """A kernel that keeps LYING (probe output mismatches the serving
    path) can never re-promote: each probe fails, backoff doubles,
    and after crush_kernel_reprobe_disable_after consecutive failures
    the quarantine goes permanent — no further probes, XLA serves
    forever, the devmon gauge says so."""
    import jax.numpy as jnp
    dm = devmon()
    m = _quarantine_mapper(
        lambda m: (lambda arrays, xs:
                   jnp.full((xs.shape[0], 3), -1, jnp.int32)))
    xs = np.arange(37, dtype=np.uint32)
    # the honest reference comes from the serving XLA path — the
    # stand-in kernel LIES by construction
    m._kernel_mode = None
    ref = np.asarray(m.map_pgs(0, xs, 3))
    m._kernel_mode = "interpret"
    m._disable_kernel("unit", RuntimeError("injected"))
    probes0 = dm.perf.dump()["quarantine_probes"]
    # failures 2 and 3: each call probes, mismatches, re-quarantines
    out, path = m.map_pgs_path(0, xs, 3)
    assert path == "xla" and np.array_equal(np.asarray(out), ref)
    assert m.kernel_quarantine_info()["state"] == "reprobing" or \
        m.kernel_quarantine_info()["failures"] == 2
    m.map_pgs(0, xs, 3)
    info = m.kernel_quarantine_info()
    assert info["state"] == "permanent"
    assert info["failures"] == 3
    assert info["next_probe_in_s"] is None
    # permanent: no more probes, ever
    m.map_pgs(0, xs, 3)
    d = dm.perf.dump()
    assert d["quarantine_probes"] - probes0 == 2
    assert d["quarantine_probe_failures"] >= 2
    assert d["quarantine_permanent_now"] >= 1
    assert m.expected_path(0, 3) == "pallas"   # still the promise
    # hygiene: clear the permanent entry so later tests see clean
    # gauges (the token table is process-global)
    dm.set_quarantine_state(m._devmon_token, None)


def test_pre_append_mpgstats_blobs_decode_zero_filled():
    """MPGStats blobs encoded BEFORE the round-14 append
    (device_health/device_engine) — reconstructed by stripping the
    empty appended containers in front of the trace context — decode
    with the new fields empty (the zero-fill discipline; the round-11
    peer_latency pin's round-14 counterpart)."""
    from ceph_tpu.mon.messages import MPGStats
    from ceph_tpu.msg.message import Message
    m = MPGStats(osd=1, epoch=2, stats={"1.0": b"x"}, slow_ops=3,
                 used_bytes=4, capacity_bytes=5, trace_spans=[b"s"],
                 peer_latency={"3": 1200}, device_health={},
                 device_engine="")
    blob = m.encode()
    assert blob[-16:] == b"\x00" * 16
    # empty map (u32 count) + empty str (u32 len) = 8 bytes
    old = blob[:-24] + blob[-16:]
    m2 = Message.decode(old)
    assert m2.device_health == {} and m2.device_engine == ""
    assert m2.peer_latency == {"3": 1200} and m2.slow_ops == 3
    # and the populated fields round-trip
    m.device_health = {"checks": 5, "mismatches": 2}
    m.device_engine = "tpu"
    again = Message.decode(m.encode())
    assert again.device_health == {"checks": 5, "mismatches": 2}
    assert again.device_engine == "tpu"


def test_cli_device_and_crash_verbs_parse():
    """New CLI verbs parse to their mon prefixes; the read-only ones
    are pinned in the read-only cap class, archive is not."""
    from ceph_tpu.bench.ceph_cli import _parse_command
    from ceph_tpu.mon.auth_monitor import READONLY_COMMANDS
    for words, prefix in [
            (["device-runtime", "status"], "device-runtime status"),
            (["crash", "ls"], "crash ls")]:
        cmd, _ = _parse_command(words)
        assert cmd["prefix"] == prefix
        assert prefix in READONLY_COMMANDS
    cmd, _ = _parse_command(["crash", "info", "x.1"])
    assert cmd == {"prefix": "crash info", "id": "x.1"}
    assert "crash info" in READONLY_COMMANDS
    cmd, _ = _parse_command(["crash", "archive", "x.1"])
    assert cmd["prefix"] == "crash archive"
    assert "crash archive" not in READONLY_COMMANDS   # it mutates


# -- the shared-cluster acceptance run --------------------------------------

DEVMON_CFG = {
    # the deployment contract under test: daemons EXPECT pallas but
    # (CPU test backend) actually serve xla — every sweep mismatches
    "devmon_expected_engine": "pallas",
    "mgr_stats_singleton_fallback": False,
    "mgr_stats_period": 0.2,
    "mon_kernel_path_confirm": 2,
    "mon_kernel_path_degraded_ratio": 0.5,
}


async def _health_checks(c):
    ret, _, out = await c.client.mon_command({"prefix": "health"})
    assert ret == 0
    return json.loads(out)["health"]["checks"]


async def _make_pool(c, name):
    """One pool creation = one new-pool full sweep (a path check) on
    every OSD's tracked mapping table."""
    await c.client.pool_create(name, pg_num=4, size=2)


def test_kernel_path_degraded_and_crash_cluster(tmp_path):
    """The tentpole acceptance run on ONE cluster: knob-forced
    expected-engine mismatch -> per-daemon counters -> /metrics row
    from reported state -> KERNEL_PATH_DEGRADED trips after the
    confirm debounce -> heals on knob flip; then crash capture ->
    RECENT_CRASH -> archive clears."""
    async def go():
        from ceph_tpu.cluster.vstart import Cluster
        from ceph_tpu.mgr.modules import PrometheusModule
        c = await Cluster(
            n_mons=1, n_osds=2, n_mgrs=1,
            config=dict(DEVMON_CFG),
            mgr_modules=[PrometheusModule]).start()
        try:
            await c.client.pool_create("d0", pg_num=4, size=2)
            await c.wait_for_clean(timeout=120)

            # every OSD's first tracked-table build swept pool d0 with
            # expected=pallas, actual=xla -> counted mismatch
            for osd in c.osds:
                d = osd.devmon.perf.dump()
                assert d["path_checks"] >= 1, d
                assert d["path_mismatch"] >= 1, d
                assert d["launches_xla"] >= 1, d

            # keep sweep traffic flowing (one pool per report window)
            # until the mon's per-report delta debounce confirms
            deadline = asyncio.get_event_loop().time() + 60
            i = 0
            while True:
                if "KERNEL_PATH_DEGRADED" in await _health_checks(c):
                    break
                assert asyncio.get_event_loop().time() < deadline, \
                    "KERNEL_PATH_DEGRADED never tripped"
                i += 1
                await _make_pool(c, f"kp-{i}")
                await asyncio.sleep(0.45)

            # the degraded table + CLI view
            ret, _, out = await c.client.mon_command(
                {"prefix": "device-runtime status"})
            assert ret == 0
            drs = json.loads(out)
            assert drs["degraded"], drs
            row = drs["daemons"].get("osd.0")
            assert row is not None, drs
            assert row["engine"] == "cpu"
            assert row["mismatches"] >= 1
            assert row["mismatch_ratio"] > 0.0
            assert row["launches"]["xla"] >= 1

            # /metrics: the mismatch row is built from REPORTED state
            # (singleton fallback disabled), per acceptance
            mgr = c.active_mgr()
            pm = next(m for m in mgr.modules
                      if m.NAME == "prometheus")
            deadline = asyncio.get_event_loop().time() + 30
            while True:
                text = await pm.render()
                rows = {}
                for line in text.splitlines():
                    if line.startswith(
                            "ceph_device_path_mismatch_total{"):
                        lab, val = line.rsplit(" ", 1)
                        rows[lab] = float(val)
                if rows.get('ceph_device_path_mismatch_total'
                            '{ceph_daemon="osd.0"}', 0) > 0:
                    break
                assert asyncio.get_event_loop().time() < deadline, \
                    f"mismatch row never appeared: {rows}"
                await asyncio.sleep(0.2)
            assert 'ceph_device_jit_compiles_total{' in text
            assert 'ceph_device_path_degraded{osd="0"' in text or \
                'ceph_device_path_degraded{osd="1"' in text
            # singleton render's label key never appears
            assert 'ceph_perf{daemon=' not in text

            # -- heal: flip the shared LIVE knob back to auto; clean
            # sweep reports clear the warning after the same confirm
            c.cfg["devmon_expected_engine"] = "auto"
            deadline = asyncio.get_event_loop().time() + 60
            while True:
                if "KERNEL_PATH_DEGRADED" not in \
                        await _health_checks(c):
                    break
                assert asyncio.get_event_loop().time() < deadline, \
                    "KERNEL_PATH_DEGRADED never cleared after heal"
                i += 1
                await _make_pool(c, f"kp-{i}")
                await asyncio.sleep(0.45)

            # the entry/exit pair is a symmetric clog discipline:
            # WRN on confirm, INF through the SAME debounce on heal
            ret, _, out = await c.client.mon_command(
                {"prefix": "log last", "num": 200})
            assert ret == 0
            lines = json.loads(out)["lines"]
            assert any(ln["level"] == "WRN" and
                       "kernel path degraded" in ln["msg"]
                       for ln in lines), lines
            assert any(ln["level"] == "INF" and
                       "kernel path healed" in ln["msg"]
                       for ln in lines), lines

            # -- crash capture on the same cluster --------------------
            from ceph_tpu.utils import crash as crash_mod
            osd = c.osds[0]

            async def _boom():
                raise RuntimeError("synthetic crash (devmon test)")

            crash_mod.watch(asyncio.ensure_future(_boom()),
                            "osd.0", osd.monc, where="unit_probe")
            deadline = asyncio.get_event_loop().time() + 20
            while True:
                ret, _, out = await c.client.mon_command(
                    {"prefix": "crash ls"})
                assert ret == 0
                crashes = json.loads(out)["crashes"]
                if crashes:
                    break
                assert asyncio.get_event_loop().time() < deadline, \
                    "crash report never reached the mon"
                await asyncio.sleep(0.1)
            rep = crashes[-1]
            assert rep["daemon"] == "osd.0"
            assert "synthetic crash" in rep["exception"]
            assert "traceback" not in rep          # ls is the summary
            assert not rep["archived"]
            assert "RECENT_CRASH" in await _health_checks(c)
            # info serves the bounded traceback
            ret, _, out = await c.client.mon_command(
                {"prefix": "crash info", "id": rep["crash_id"]})
            assert ret == 0
            info = json.loads(out)
            assert "RuntimeError" in info["traceback"]
            assert len(info["traceback"]) <= 4000
            # the local ring kept it too (the asok/debug view)
            assert any(r["crash_id"] == rep["crash_id"]
                       for r in crash_mod.recent_crashes())
            # archive acks: the warning clears, the record stays
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "crash archive", "id": rep["crash_id"]})
            assert ret == 0, rs
            assert "RECENT_CRASH" not in await _health_checks(c)
            ret, _, out = await c.client.mon_command(
                {"prefix": "crash ls"})
            assert json.loads(out)["crashes"][-1]["archived"] is True

            # the asok device block serves the daemon+process views
            status = osd.devmon.dump()
            assert status["expected_engine"] == "auto"
            assert status["counters"]["path_mismatch"] >= 1
        finally:
            await c.stop()
    run(go())


def test_device_storm_cluster():
    """The round-16 acceptance run: jit_fail / jit_stall / bad_result
    bursts at the devmon chokepoint under concurrent replicated + EC
    client writes — ZERO client-visible errors, counters prove the
    kernel path was quarantined AND re-promoted (not just degraded),
    a poisoned EC encode is absorbed by the degrade ladder, and every
    acked byte reads back bit-identical on settle."""
    async def go():
        from ceph_tpu.cluster.vstart import Cluster
        from ceph_tpu.sim.thrasher import Thrasher
        c = await Cluster(n_mons=1, n_osds=4,
                          config={"mon_osd_down_out_interval": 2.0}
                          ).start()
        try:
            await c.client.pool_create("rp", pg_num=4, size=2)
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "osd erasure-code-profile set",
                 "name": "kprof",
                 "profile": ["k=2", "m=1", "crush-failure-domain=osd",
                             "stripe_unit=1024"]})
            assert ret == 0, rs
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "osd pool create", "pool": "ecpool",
                 "pg_num": 4, "pool_type": "erasure",
                 "erasure_code_profile": "kprof"})
            assert ret == 0, rs
            await c.wait_for_clean(timeout=240)
            io = await c.client.open_ioctx("rp")
            io_ec = await c.client.open_ioctx("ecpool")

            th = Thrasher(c, seed=16, min_live_osds=4)
            summary = await th.device_storm(io, io_ec, ec_writes=6)

            # zero client-visible errors is asserted INSIDE the storm;
            # the counters prove the full quarantine cycle happened
            assert summary["write_errors"] == 0
            assert summary["ec_writes_acked"] == 6
            assert summary["quarantine_entries"] >= 1
            assert summary["quarantine_exits"] >= 1
            assert summary["probes"] >= 2           # refused + clean
            assert summary["probe_failures"] >= 1   # the bad_result
            assert summary["repromoted_path"] == "pallas-interpret"
            assert summary["ec_degraded_ops"] >= 1  # ladder engaged
            assert summary["faults_injected"] >= 2
            await th.settle_and_verify(io)

            # the quarantine evidence reached the mon's status surface
            ret, _, out = await c.client.mon_command(
                {"prefix": "device-runtime status"})
            assert ret == 0
            drs = json.loads(out)
            row = drs["daemons"].get("osd.0")
            assert row is not None and "quarantine" in row, drs
        finally:
            await c.stop()
    run(go())


# -- the compile listener: compiles, not first calls (round 26) --------------

def test_xla_compile_listener_counts_compiles_not_first_calls():
    """Two ``jax.jit`` objects of one function and shape are two first
    calls by devmon's book; what the backend compiled is what the
    ``jax.monitoring`` listener saw, filed under the ``jit_call`` in
    progress, and the ``jit_compile`` span carries its seconds."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.utils.devmon import compile_events

    dm = devmon()
    tracer = Tracer("devmon-xla", {"trace_slow_keep_s": 0.0})
    old_tracer = dm.tracer
    dm.attach_tracer(tracer)
    try:
        def f(x):
            return (x * 3 + 1).sum()

        x = jnp.arange(1237, dtype=jnp.int32)       # a shape of its own
        jax.block_until_ready(x)
        a, b = jax.jit(f), jax.jit(f)
        before = dm.perf.dump()
        n_events = len(compile_events())
        for fn in (a, b):
            assert int(dm.jit_call("ec_probe", (id(fn), x.shape),
                                   fn, x)) == int(f(np.arange(1237)))
        # warm by both books
        dm.jit_call("ec_probe", (id(a), x.shape), a, x)
        after = dm.perf.dump()
        assert after["jit_compiles"] - before["jit_compiles"] == 2
        mine = [ev for ev in compile_events()[n_events:]
                if ev[4] == "ec_probe"]
        true_count = len(mine)
        assert 1 <= true_count <= 2
        assert after["xla_compiles"] - before["xla_compiles"] \
            >= true_count
        for at_ns, fun, seconds, cached, program in mine:
            assert "f" in fun and seconds > 0.0 and not cached
            assert at_ns <= time.perf_counter_ns()
        assert after["xla_compile_seconds"] > \
            before["xla_compile_seconds"]
        # one jit_compile span per call that compiled, true duration
        spans = [s for s in tracer.dump()["spans"]
                 if s["name"] == "jit_compile"]
        assert len(spans) == true_count
        assert spans[0]["tags"]["fn"] == "ec_probe"
        assert spans[0]["tags"]["cached"] is False
        assert spans[0]["duration"] == pytest.approx(mine[0][2],
                                                     abs=1e-6)
        # a compile under no jit_call is counted, under "other"
        n_events = len(compile_events())
        jax.block_until_ready(jax.jit(lambda v: v - 7)(x))
        assert [ev[4] for ev in compile_events()[n_events:]] == ["other"]
    finally:
        dm.attach_tracer(old_tracer)

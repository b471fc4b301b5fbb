"""End-to-end distributed op tracing (round 9).

Acceptance surface:

- a single replicated-pool client write at ``trace_sampling_rate=1.0``
  yields ONE mgr-reassembled trace containing client, primary,
  >=2 replica, and objectstore-commit spans with correct parent links
  and non-overlapping phase durations summing ~= the client-observed
  latency;
- an artificially delayed op BELOW the sampling rate is still
  retained via the slow-op tail path (``trace_slow_keep_s``);
- ``PrometheusModule.render`` emits the per-op-class latency
  histograms as valid exposition-format ``le``-bucketed series with
  monotone cumulative buckets (pinned in tests/test_meta.py's parser
  guard; exercised against a LIVE cluster here);
- a storm smoke proves tracing survives kill/revive.
"""

import asyncio
import json
import time

import pytest

from ceph_tpu.cluster.vstart import Cluster
from ceph_tpu.mgr.modules import TracingModule
from ceph_tpu.sim import faults as F
from ceph_tpu.utils import tracing
from ceph_tpu.utils.tracing import Tracer, TraceIndex


def run(coro):
    asyncio.run(coro)


# -- unit: sampling + tail retention semantics -----------------------------

def test_tracer_head_sampling_and_propagation():
    t = Tracer("client", {"trace_sampling_rate": 1.0})
    root = t.start_root("client_op", tags={"oid": "o"})
    assert root is not None and root.trace_id != 0
    child = root.child("queue")
    assert child.trace_id == root.trace_id
    assert child.parent_span_id == root.span_id
    child.finish()
    root.finish()
    assert t.ship_pending() == 2
    # context rides a message; the receiver's span links to the sender
    from ceph_tpu.osd.messages import MOSDOp
    m = MOSDOp(tid=1, oid="o")
    m.set_trace(root)
    rx = Tracer("osd.0", {})
    span = rx.from_msg("osd_op", m)
    assert span is not None and span.trace_id == root.trace_id
    assert span.parent_span_id == root.span_id


def test_tracer_tail_retention_and_off_path():
    # unsampled but slow: retained with a post-hoc trace id
    t = Tracer("client", {"trace_sampling_rate": 0.0,
                          "trace_slow_keep_s": 0.01})
    slow = t.start_root("client_op")
    assert slow is not None and slow.trace_id == 0   # local-only
    time.sleep(0.02)
    slow.finish()
    d = t.dump()
    assert slow.trace_id != 0
    assert d["slow_spans"] and \
        d["slow_spans"][0]["tags"]["tail_sampled"]
    # unsampled and fast: dropped
    fast = t.start_root("client_op")
    fast.finish()
    assert len(t.dump()["spans"]) == 1
    # fully off (slow_keep <= 0): no span objects at all
    off = Tracer("client", {"trace_sampling_rate": 0.0,
                            "trace_slow_keep_s": 0.0})
    assert off.start_root("client_op") is None
    # unsampled context never propagates
    from ceph_tpu.osd.messages import MOSDOp
    m = MOSDOp(tid=1, oid="o")
    m.set_trace(t.start_root("client_op"))
    assert m.trace_id == 0


def test_trace_index_survives_malformed_spans():
    """Span blobs arrive from arbitrary clients (MTraceReport is an
    uncapped report): a mistyped field must drop at add(), never
    poison ls()/show() for every later caller."""
    idx = TraceIndex()
    idx.add({"trace_id": 1, "span_id": 2, "start": "not-a-float"})
    idx.add({"trace_id": 5, "span_id": 7, "parent_span_id": 9})
    idx.add({"trace_id": "x", "span_id": 1})
    idx.add({"trace_id": 3, "span_id": 4, "parent_span_id": 0,
             "name": "ok", "service": "client", "start": 1.0,
             "duration": 0.5, "tags": "not-a-dict"})
    rows = idx.ls()          # must not raise
    assert [r["trace_id"] for r in rows if r["root"] == "ok"]
    missing_fields = idx.show(5)
    if missing_fields is not None:       # kept with defaults is fine
        assert missing_fields["duration"] >= 0.0
    ok = idx.show(3)
    assert ok["tree"][0]["tags"] == {}


def test_trace_index_per_trace_span_cap_and_deep_chain():
    """One hostile trace_id cannot grow the index without bound, and
    a parent chain deeper than the serve cap must not drive show()'s
    recursion toward the interpreter limit."""
    idx = TraceIndex()
    for i in range(TraceIndex.MAX_SPANS_PER_TRACE + 50):
        idx.add({"trace_id": 1, "span_id": i + 1,
                 "parent_span_id": i, "name": "chain",
                 "service": "evil", "start": float(i),
                 "duration": 0.0, "tags": {}})
    ent = idx.traces[1]
    assert len(ent["spans"]) == TraceIndex.MAX_SPANS_PER_TRACE
    show = idx.show(1)          # must not raise RecursionError
    depth = 0
    node = show["tree"][0]
    while node["children"]:
        node = node["children"][0]
        depth += 1
    assert depth <= TraceIndex.MAX_TREE_DEPTH + 1


def test_trace_index_bounds_and_slowest_first():
    idx = TraceIndex(max_traces=4)
    for i in range(8):
        idx.add({"trace_id": i + 1, "span_id": 100 + i,
                 "parent_span_id": 0, "name": "client_op",
                 "service": "client", "start": float(i),
                 "duration": float(i) / 100.0, "tags": {}})
    assert len(idx.traces) == 4                  # oldest evicted
    rows = idx.ls()
    durs = [r["duration"] for r in rows]
    assert durs == sorted(durs, reverse=True)    # slowest first


# -- the acceptance trace: one replicated write, fully decomposed ----------

def _flatten(node, out):
    out.append(node)
    for c in node["children"]:
        _flatten(c, out)


def _find(nodes, name):
    return [n for n in nodes if n["name"] == name]


def test_replicated_write_trace_reassembly(tmp_path):
    async def go():
        c = await Cluster(
            n_mons=1, n_osds=3,
            config={"trace_sampling_rate": 1.0,
                    "mgr_tracing_interval": 0.25,
                    "admin_socket_dir": str(tmp_path)},
            mgr_modules=[TracingModule]).start()
        try:
            await c.client.pool_create("t", pg_num=8, size=3,
                                       min_size=2)
            await c.wait_for_clean(timeout=120)
            io = await c.client.open_ioctx("t")
            # warm the connection path: the FIRST write pays messenger
            # connect + auth handshakes, which are client-side time no
            # OSD phase can account for
            await io.write_full("warm-obj", b"w" * 4096)
            t0 = time.monotonic()
            await io.write_full("traced-obj", b"x" * 4096)
            observed = time.monotonic() - t0
            mod = c.mgr.modules[0]
            trace = None
            deadline = asyncio.get_event_loop().time() + 20
            while trace is None:
                for row in mod.trace_ls(limit=10):
                    cand = mod.trace_show(row["trace_id"])
                    if row["root"] == "client_op" and \
                            row["num_spans"] >= 6 and \
                            cand["tree"][0]["tags"].get("oid") == \
                            "traced-obj":
                        trace = cand
                        break
                if trace is None:
                    assert asyncio.get_event_loop().time() < \
                        deadline, (
                        "mgr never reassembled the write trace: "
                        f"{mod.trace_ls(limit=10)}")
                    await asyncio.sleep(0.1)

            spans: list[dict] = []
            assert len(trace["tree"]) == 1, trace
            _flatten(trace["tree"][0], spans)
            root = trace["tree"][0]
            assert root["name"] == "client_op" and \
                root["service"] == "client"
            # primary: one osd_op child with queue + execute phases
            (osd_op,) = _find(root["children"], "osd_op")
            primary_svc = osd_op["service"]
            assert primary_svc.startswith("osd.")
            (queue,) = _find(osd_op["children"], "queue")
            (execute,) = _find(osd_op["children"], "execute")
            # execute decomposes into local store commit + repop wait
            (local_commit,) = _find(execute["children"],
                                    "objectstore_commit")
            assert local_commit["service"] == primary_svc
            (rep_subop_wait,) = _find(execute["children"], "rep_subop_wait")
            # >= 2 replica apply spans from DISTINCT non-primary osds,
            # each with its own objectstore commit
            applies = _find(rep_subop_wait["children"], "repop_apply")
            svcs = {a["service"] for a in applies}
            assert len(applies) >= 2 and len(svcs) >= 2, applies
            assert primary_svc not in svcs
            for a in applies:
                assert _find(a["children"], "objectstore_commit"), a
            commits = _find(spans, "objectstore_commit")
            assert len(commits) >= 3        # primary + both replicas
            # phase durations: non-overlapping children sum to ~= the
            # parent, and the primary's phases fit inside the
            # client-observed latency
            assert queue["duration"] + execute["duration"] <= \
                osd_op["duration"] + 0.010
            assert osd_op["duration"] <= root["duration"] + 0.005
            assert root["duration"] <= observed + 0.005
            phase_sum = queue["duration"] + execute["duration"]
            assert observed - phase_sum < 1.0, (
                "client latency unaccounted for: "
                f"{observed} vs phases {phase_sum}")
            for a in applies:
                assert a["duration"] <= rep_subop_wait["duration"] + 0.010
            assert trace["phases"]["objectstore_commit"] >= 0.0

            # -- `ceph trace ls/show` (the mon-side CLI view) ---------
            ret, _, out = await c.client.mon_command(
                {"prefix": "trace ls"})
            assert ret == 0
            rows = json.loads(out)["traces"]
            durs = [r["duration"] for r in rows]
            assert durs == sorted(durs, reverse=True)
            tid = rows[0]["trace_id"]
            ret, _, out = await c.client.mon_command(
                {"prefix": "trace show", "trace_id": tid})
            assert ret == 0 and json.loads(out)["trace_id"] == tid
            ret, rs, _ = await c.client.mon_command(
                {"prefix": "trace show", "trace_id": 424242})
            assert ret == -2, rs

            # -- asok surfaces: dump_tracing + perf histogram dump ----
            from ceph_tpu.utils.admin_socket import daemon_command
            dump = await daemon_command(
                f"{tmp_path}/osd.{c.osds[0].whoami}.asok",
                "dump_tracing")
            assert dump["sampling_rate"] == 1.0
            assert dump["buffered"] >= 1 or dump["pending_ship"] >= 0
            hist = await daemon_command(
                f"{tmp_path}/osd.{c.osds[0].whoami}.asok",
                "perf histogram dump")
            assert any(
                counters.get("op_w_latency_hist", {}).get("count", 0)
                > 0 and counters["op_w_latency_hist"]["buckets"]
                for name, counters in hist.items()
                if name.startswith("osd.")), hist

            # -- live prometheus render carries the histogram series --
            from ceph_tpu.mgr.modules import PrometheusModule
            prom = PrometheusModule(c.mgr)
            text = await prom.render()
            assert "ceph_perf_hist_bucket{" in text
            assert 'counter="op_w_latency_hist"' in text
        finally:
            await c.stop()
    run(go())


# -- tail path: a delayed op below the sampling rate is still kept ---------

def test_slow_op_retained_below_sampling_rate():
    async def go():
        c = await Cluster(
            n_mons=1, n_osds=3,
            config={"trace_sampling_rate": 0.0,
                    "trace_slow_keep_s": 0.2}).start()
        try:
            await c.client.pool_create("t", pg_num=8, size=3,
                                       min_size=2)
            await c.wait_for_clean(timeout=120)
            io = await c.client.open_ioctx("t")
            await io.write_full("fast-obj", b"y")     # under threshold
            inj = F.FaultInjector(seed=5)
            c.install_faults(inj)
            inj.install("lag",
                        [F.delay("client.*", "osd.*", 0.35)])
            t0 = time.monotonic()
            await io.write_full("slow-obj", b"z" * 128)
            assert time.monotonic() - t0 >= 0.2
            inj.clear("lag")
            lead = c.leader()
            deadline = asyncio.get_event_loop().time() + 10
            tail = []
            while not tail:
                # (a cold process's first crush_sweep compiles and is
                # tail-kept too: it is not the op this test delays)
                tail = [s for _, s in lead.trace_spans
                        if s.get("tags", {}).get("tail_sampled")
                        and s["name"] == "client_op"]
                if not tail:
                    assert asyncio.get_event_loop().time() < \
                        deadline, list(lead.trace_spans)
                    await asyncio.sleep(0.1)
            assert tail[0]["name"] == "client_op"
            assert tail[0]["duration"] >= 0.2
            assert tail[0]["tags"].get("slow")
        finally:
            await c.stop()
    run(go())


# -- metadata path: client -> MDS spans reassemble -------------------------

def test_metadata_op_trace_reassembly():
    async def go():
        c = await Cluster(
            n_mons=1, n_osds=3,
            config={"trace_sampling_rate": 1.0}).start()
        try:
            await c.start_fs(pool="cephfs", n_mds=1, timeout=120)
            from ceph_tpu.cephfs.client import CephFSClient
            # config threads through to the owned objecter's tracer —
            # without it the cluster's sampling knob never reaches
            # this client and no metadata root is ever created
            cl = await CephFSClient.create(
                c.client.monc.monmap, None, "cephfs",
                keyring=c.keyring, config=c.cfg)
            await cl.mkdir("/traced")
            await cl.unmount()
            lead = c.leader()
            deadline = asyncio.get_event_loop().time() + 15
            found = None
            while found is None:
                for row in lead.trace_index.ls(limit=20):
                    if row["root"] == "mds_req" and any(
                            s.startswith("mds.")
                            for s in row["services"]):
                        found = lead.trace_index.show(
                            row["trace_id"])
                        break
                if found is None:
                    assert asyncio.get_event_loop().time() < \
                        deadline, lead.trace_index.ls(limit=20)
                    await asyncio.sleep(0.1)
            root = found["tree"][0]
            assert root["name"] == "mds_req" and \
                root["service"] == "client"
            (mds_op,) = [n for n in root["children"]
                         if n["name"] == "mds_op"]
            assert mds_op["service"].startswith("mds.")
            assert mds_op["tags"]["op"] in ("mkdir",)
            assert mds_op["duration"] <= root["duration"] + 0.010
        finally:
            await c.stop()
    run(go())


# -- storm smoke: tracing survives kill/revive -----------------------------

def test_tracing_survives_thrash_smoke():
    from ceph_tpu.sim.thrasher import Thrasher

    async def go():
        c = await Cluster(
            n_mons=1, n_osds=4,
            config={"trace_sampling_rate": 1.0,
                    "mon_osd_down_out_interval": 600.0}).start()
        try:
            await c.client.pool_create("t", pg_num=8, size=3,
                                       min_size=2)
            await c.wait_for_clean(timeout=120)
            io = await c.client.open_ioctx("t")
            th = Thrasher(c, seed=77, min_live_osds=3)
            await th.thrash(io, steps=12)
            summary = await th.settle_and_verify(io, timeout=300)
            assert summary["acked_writes"] > 0
            # spans flowed through the storm and the pool survived the
            # kill/revive churn: slowest-first listing still serves
            lead = c.leader()
            assert lead is not None and len(lead.trace_spans) > 0
            ret, _, out = await c.client.mon_command(
                {"prefix": "trace ls", "limit": 5})
            assert ret == 0
            rows = json.loads(out)["traces"]
            assert rows, "no reassembled traces after the storm"
            durs = [r["duration"] for r in rows]
            assert durs == sorted(durs, reverse=True)
        finally:
            await c.stop()
    run(go())


# -- OpTracker monotonic satellite ----------------------------------------

def test_op_tracker_monotonic_and_config_knobs():
    from ceph_tpu.utils.config import Config
    from ceph_tpu.utils.op_tracker import OpTracker

    cfg = Config()
    assert cfg.get("osd_op_history_size") == 20
    assert cfg.get("osd_op_complaint_time") == 30.0
    t = OpTracker()
    assert t.history.maxlen == 20 and t.slow_op_warn_s == 30.0
    op = t.create("probe")
    # the age base is monotonic, not wall: a wall-clock jump cannot
    # corrupt it (initiated_at stays wall for display)
    assert abs(op.initiated_at - time.time()) < 5.0
    assert op.start <= time.monotonic()
    op.mark_event("phase")
    op.finish()
    d = op.dump()
    assert d["age"] >= 0 and d["events"][0]["time"] == 0.0
    assert all(e["time"] >= 0 for e in d["events"])
    t2 = OpTracker(history_size=3, slow_op_warn_s=0.0)
    for i in range(5):
        t2.create(f"op{i}").finish()
    assert len(t2.history) == 3


# -- one clock, two kinds, capture (round 26) -------------------------------

def test_span_stamps_share_one_clock_and_dump_keeps_its_shape():
    t = Tracer("client", {"trace_sampling_rate": 1.0})
    before = time.perf_counter_ns()
    wall = time.time()
    root = t.start_root("client_op", tags={"oid": "o"})
    time.sleep(0.01)
    root.finish()
    after = time.perf_counter_ns()
    # both ends are perf_counter_ns stamps, the duration their difference
    assert before <= root.t0_ns < root.t1_ns <= after
    assert root.duration == (root.t1_ns - root.t0_ns) / 1e9 >= 0.01
    # the wall start is derived from them through one offset
    assert abs(root.start - wall) < 0.05
    assert root.start == tracing.wall_of(root.t0_ns)
    d = root.dump()
    assert set(d) == {"trace_id", "span_id", "parent_span_id", "name",
                      "service", "kind", "start", "duration", "tags"}
    assert d["kind"] == "interval" and d["service"] == "client"
    assert isinstance(d["start"], float) and d["duration"] >= 0.01
    # a back-dated sub-phase nests inside its parent on the same clock
    root.annotate("kv_commit", 0.004)
    kv = t.dump()["spans"][-1]
    assert kv["name"] == "kv_commit" and kv["start"] == d["start"]
    assert kv["duration"] == 0.004
    # the index keeps the kind and shows it
    idx = TraceIndex()
    idx.add(d)
    assert idx.show(root.trace_id)["tree"][0]["kind"] == "interval"


def test_section_nesting_gives_the_outer_its_self_time():
    t = Tracer("osd.0", {"trace_sampling_rate": 1.0})
    root = t.start_root("client_op")
    with tracing.section("osd.ec_fanout", root) as outer:
        time.sleep(0.004)
        with tracing.section("objectstore_commit", root) as inner:
            inner.tag("osd", 0)
            time.sleep(0.006)
        time.sleep(0.002)
    assert outer.kind == inner.kind == "section"
    assert outer.thread == inner.thread != 0
    assert outer.trace_id == root.trace_id
    assert outer.parent_span_id == inner.parent_span_id == root.span_id
    # the inner one lies inside the outer one on the one clock
    assert outer.t0_ns <= inner.t0_ns < inner.t1_ns <= outer.t1_ns
    self_s = outer.duration - inner.duration
    assert 0.006 <= self_s < outer.duration and inner.duration >= 0.006
    # both reach the operator's buffer, kind shown
    kinds = {s["name"]: s["kind"] for s in t.dump()["spans"]}
    assert kinds == {"objectstore_commit": "section",
                     "osd.ec_fanout": "section"}
    # nobody looking: one shared object, nothing recorded
    off = Tracer("osd.1", {})
    a = tracing.section("msg.send", None, off)
    b = tracing.section("msg.recv", None, off)
    assert a is b and not a
    with a as s:
        s.tag("bytes", 1).finish()
    assert off.dump()["spans"] == []


def _captured() -> list[dict]:
    """The capture's records as dicts (tags too)."""
    return [tracing.record_dict(r) for r in tracing.captured()]


def test_capture_follows_the_profiler_session(tmp_path):
    import jax
    assert not tracing.capturing()
    n_before = len(tracing.captured())
    off = Tracer("client", {"trace_slow_keep_s": 0.0})
    with tracing.section("client.submit", None, off):
        pass
    assert off.start_root("client_op") is None
    assert len(tracing.captured()) == n_before     # the off path
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.capturing()
        assert tracing.captured() == []            # a new session
        root = off.start_root("client_op")         # sampled as if rate 1
        assert root is not None
        assert root.trace_id & tracing.CAPTURE_ONLY
        with tracing.section("client.submit", root) as sec:
            sec.tag("bytes", 7)
        with tracing.section("msg.recv", None, None, "osd.3"):
            pass                                   # nobody's op: kept too
        root.finish()
    finally:
        jax.profiler.stop_trace()
    assert not tracing.capturing()
    recs = _captured()
    assert [(r["kind"], r["name"]) for r in recs] == [
        ("section", "client.submit"), ("section", "msg.recv"),
        ("interval", "client_op")]
    assert recs[0]["tags"] == {"bytes": 7}
    assert recs[0]["parent_span_id"] == recs[2]["span_id"]
    assert recs[1]["service"] == "osd.3" and recs[1]["trace_id"] == 0
    assert recs[2]["t0_ns"] <= recs[0]["t0_ns"] <= recs[0]["t1_ns"] \
        <= recs[2]["t1_ns"]
    info = tracing.capture_info()
    assert info["dropped"] == 0 and info["records"] == 3
    (th,) = info["threads"].values()
    assert th["cpu_ns"][0] <= th["cpu_ns"][1]
    assert th["clock_ns"][0] <= th["clock_ns"][1]
    # capture-only traces never reach the operator's buffers
    assert off.dump()["spans"] == [] and off.ship_pending() == 0
    # after the session the list stays, and nothing more is appended
    with tracing.section("client.submit", None, off):
        pass
    assert len(tracing.captured()) == 3


# every span of the served EC path's table (ISSUE 26), old names and new
EC_WRITE_SPANS = {
    "client_op", "client.submit", "client.reply", "msg.encode",
    "msg.send", "msg.recv", "msg.decode", "osd_op", "queue", "execute",
    "osd.dispatch", "osd.ec_prepare", "osd.ec_fanout", "ec_subop_wait",
    "ec_sub_write", "ec.agg_wait", "ec.pack", "ec.launch",
    "ec.device_wait", "objectstore_commit", "osd.reply"}
EC_READ_SPANS = {
    "client_op", "client.submit", "client.reply", "msg.encode",
    "msg.send", "msg.recv", "msg.decode", "osd_op", "queue", "execute",
    "osd.dispatch", "osd.ec_subread_wait", "osd.ec_sub_read",
    "osd.ec_assemble", "ec.cache_lookup", "ec.agg_wait", "ec.pack",
    "ec.launch", "ec.device_wait", "store.read", "osd.reply"}


async def _ec_cluster(config, **kw):
    c = await Cluster(n_mons=1, n_osds=6,
                      config=dict({"osd_ec_resident_bytes": 8 << 20},
                                  **config), **kw).start()
    ret, rs, _ = await c.client.mon_command(
        {"prefix": "osd erasure-code-profile set", "name": "p32",
         "profile": ["k=3", "m=2", "plugin=jax",
                     "technique=reed_sol_van", "stripe_unit=4096"]})
    assert ret == 0, rs
    ret, rs, _ = await c.client.mon_command(
        {"prefix": "osd pool create", "pool": "ec", "pg_num": 4,
         "pool_type": "erasure", "erasure_code_profile": "p32"})
    assert ret == 0, rs
    await c.wait_for_clean(timeout=120)
    return c, await c.client.open_ioctx("ec")


async def _degrade(c, oid: str):
    """Kill the OSD that holds the object's first data shard; reads of
    it decode from then on."""
    ret, _, out = await c.client.mon_command(
        {"prefix": "osd map", "pool": "ec", "object": oid})
    assert ret == 0
    victim = json.loads(out)["acting"][1]     # a data shard, not the
    await c.kill_osd(victim)                  # primary's own
    await c.wait_for_osd_down(victim, timeout=60)


def test_ec_write_and_degraded_read_capture_every_span(tmp_path):
    """One EC write_full and one degraded read under a profiler session
    yield every span of the table, each with its parent in the same
    trace, and every section inside the client_op root's interval."""
    import jax
    payload = bytes(range(256)) * 192            # 48 KiB: 4 stripes

    async def go():
        c, io = await _ec_cluster({})
        try:
            await io.write_full("warm", payload)      # connections up
            await io.write_full("victim", payload)
            await _degrade(c, "victim")
            assert await io.read("victim") == payload  # programs warm
            for o in c.osds:                     # the next read gathers
                if o.ec_resident is not None:
                    o.ec_resident.clear()
            jax.profiler.start_trace(str(tmp_path))
            try:
                await io.write_full("traced", payload)
                assert await io.read("victim") == payload
            finally:
                jax.profiler.stop_trace()
        finally:
            await c.stop()
    run(go())
    recs = _captured()
    assert tracing.capture_info()["dropped"] == 0
    roots = [r for r in recs if r["name"] == "client_op"]
    assert [r["tags"]["op_class"] for r in roots] == ["write", "read"]
    for root, want in zip(roots, (EC_WRITE_SPANS, EC_READ_SPANS)):
        assert root["trace_id"] & tracing.CAPTURE_ONLY
        mine = [r for r in recs if r["trace_id"] == root["trace_id"]]
        assert want <= {r["name"] for r in mine}, \
            want - {r["name"] for r in mine}
        ids = {r["span_id"] for r in mine}
        for r in mine:
            if r is not root:
                assert r["parent_span_id"] in ids, r
            if r["kind"] == "section":
                assert root["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] \
                    <= root["t1_ns"], (r["name"], root["name"])
        # what the old names were is what they are
        kind = {r["name"]: r["kind"] for r in mine}
        assert kind["queue"] == kind["execute"] == "interval"
        assert kind["ec.agg_wait"] == "interval"
        assert kind["msg.send"] == kind["ec.launch"] == "section"
    wkind = {r["name"]: r["kind"] for r in recs
             if r["trace_id"] == roots[0]["trace_id"]}
    assert wkind["objectstore_commit"] == "section"
    assert wkind["ec_subop_wait"] == wkind["ec_sub_write"] == "interval"
    # the write's two ec_prepare stretches: the closing one says where
    # the k+m _hcrc stamps came from (the fused pass's folded row CRCs)
    prep = [r["tags"] for r in recs if r["name"] == "osd.ec_prepare"
            and r["trace_id"] == roots[0]["trace_id"]]
    assert prep == [{"stripes": 4},
                    {"stripes": 4, "hcrc": "device_rows"}]


def test_ec_spans_reach_trace_show_on_the_operators_path():
    """No profiler session, trace_sampling_rate 1.0: the same spans
    reach the mgr's index (``ceph trace show``)."""
    payload = bytes(range(256)) * 192

    async def go():
        c, io = await _ec_cluster(
            {"trace_sampling_rate": 1.0, "mgr_tracing_interval": 0.25},
            mgr_modules=[TracingModule])
        try:
            await io.write_full("warm", payload)
            await io.write_full("victim", payload)
            await _degrade(c, "victim")
            for o in c.osds:
                if o.ec_resident is not None:
                    o.ec_resident.clear()
            assert not tracing.capturing()
            n_cap = len(tracing.captured())
            await io.write_full("traced", payload)
            assert await io.read("victim") == payload
            assert len(tracing.captured()) == n_cap
            mod = c.mgr.modules[0]
            deadline = asyncio.get_event_loop().time() + 30
            found = {}
            while len(found) < 2:
                for row in mod.trace_ls(limit=50):
                    show = mod.trace_show(row["trace_id"])
                    if row["root"] != "client_op" or not show["tree"]:
                        continue
                    tags = show["tree"][0]["tags"]
                    flat: list[dict] = []
                    _flatten(show["tree"][0], flat)
                    names = {n["name"] for n in flat}
                    if tags.get("oid") == "traced" and \
                            EC_WRITE_SPANS <= names:
                        found["write"] = flat
                    if tags.get("oid") == "victim" and \
                            tags.get("op_class") == "read" and \
                            EC_READ_SPANS <= names:
                        found["read"] = flat
                if len(found) < 2:
                    assert asyncio.get_event_loop().time() < deadline, (
                        sorted(found), mod.trace_ls(limit=50))
                    await asyncio.sleep(0.2)
            kinds = {n["name"]: n["kind"] for n in found["write"]}
            assert kinds["osd.ec_prepare"] == "section"
            assert kinds["ec_subop_wait"] == "interval"
        finally:
            await c.stop()
    run(go())


def test_ec_pool_feeds_commit_and_apply_latency():
    """`ceph osd perf` on an EC cluster: every acting OSD's
    apply_latency counts its sub-writes, the primaries' commit_latency
    counts the writes."""
    async def go():
        c, io = await _ec_cluster({})
        try:
            n = 12
            for i in range(n):
                await io.write_full(f"obj-{i}", bytes([i]) * 20000)
            lat = {o.whoami: o.perf.dump() for o in c.osds}
            acting = set()
            for o in c.osds:
                for pg in o.pgs.values():
                    if getattr(pg, "ec", None) is not None:
                        acting.update(a for a in pg.acting if a >= 0)
            assert acting
            for osd in acting:
                assert lat[osd]["apply_latency"]["avgcount"] > 0, osd
                assert lat[osd]["apply_latency"]["sum"] > 0.0
            commits = sum(d["commit_latency"]["avgcount"]
                          for d in lat.values())
            assert commits == n
            applies = sum(d["apply_latency"]["avgcount"]
                          for d in lat.values())
            assert applies == n * 5                     # k + m each
        finally:
            await c.stop()
    run(go())

"""The replicated cell's own checks: ``correct`` comes out false for its
control ("acknowledged with 2 of 3") and when the replicated backend is
broken underneath a run; its two metric readers read what the driver
and the program leave, and nothing from a program without them."""

import json
import types

import pytest

import control
from drivers import rados_bench_rep
from harness import runner
from reference import rep_ref
from test_rehearsal import CELLS, run
from test_rep_ref import POOL, hosts_map

CELL = "rep3-write-4m-t16"


def _sound(ctx, driver):
    """What the reference itself gives for the cell's objects: every
    copy on its OSD, every read the bytes written, 3 copies at the ack."""
    p = driver.Params(ctx.config, ctx.traffic)
    p.make_payloads(ctx.seed)
    pool = rep_ref.Pool(dict(POOL, pg_num=int(ctx.config["pg_num"])),
                        hosts_map(int(ctx.config["osds"])))
    name = "benchmark_data_{}".format
    answers, at_ack, placed = [], {}, {}
    for i in range(int(ctx.traffic["check_objects"])):
        acting, want = pool.acting(name(i)), p.payloads.get(i)
        answers.append({
            "object": i, "pgid": pool.pgid(name(i)), "acting": acting,
            "stored": {o: (pool.pgid(name(i)), want) for o in acting},
            "read": want})
        at_ack[i], placed[i] = p.copies, list(acting)
    return p, pool, name, answers, at_ack, placed


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_the_control_acknowledged_with_2_of_3_is_not_correct(seed):
    ctx, driver = control._context(CELL, seed, rehearsal=True)
    p, pool, name, answers, at_ack, placed = _sound(ctx, driver)
    driver.compare(ctx, p, pool, name, answers, at_ack, placed)
    assert ctx.compared.ok                   # the sound answer passes
    ctx, driver = control._context(CELL, seed, rehearsal=True)
    short = {i: p.copies - 1 for i in at_ack}
    driver.compare(ctx, p, pool, name, answers, short, placed)
    assert not ctx.compared.ok
    rows = ctx.compared.rows
    assert rows["replicas_missing_at_ack"]["value"] == len(at_ack)
    assert all(r["value"] == 0 for n, r in rows.items()
               if n != "replicas_missing_at_ack")


@pytest.mark.parametrize("what,number", [
    ("copy_lost", "replicas_missing"), ("copy_altered", "replicas_differing"),
    ("copy_elsewhere", "replicas_misplaced"),
    ("device_placement_off", "replicas_misplaced"),
    ("read_altered", "reads_differing")])
def test_each_broken_answer_moves_its_own_number(what, number):
    ctx, driver = control._context(CELL, 7, rehearsal=True)
    p, pool, name, answers, at_ack, placed = _sound(ctx, driver)
    a = answers[0]
    first = a["acting"][0]
    spare = next(o for o in range(12) if o not in a["acting"])
    if what == "copy_lost":
        del a["stored"][first]
    elif what == "copy_altered":
        a["stored"][first] = (a["pgid"], b"\x00" + a["read"][1:])
    elif what == "copy_elsewhere":
        a["stored"][spare] = (a["pgid"], a["read"])
    elif what == "device_placement_off":
        placed[0] = [spare] + placed[0][1:]
    else:
        a["read"] = a["read"][:-1] + b"\x00"
    driver.compare(ctx, p, pool, name, answers, at_ack, placed)
    over = {n for n, r in ctx.compared.rows.items() if r["value"] > 0}
    assert over == {number}


def test_the_reference_maps_on_the_configurations_layout():
    # the driver's reading of the configuration file against the tree
    # written out by hand: 12 hosts of one OSD, rule over hosts, size 3
    ctx, driver = control._context(CELL, 1, rehearsal=False)
    pool, crush, weights = driver.expected(ctx.config, 1)
    by_hand = dict(hosts_map(12), tunables=dict(rep_ref.crush_ref.JEWEL))
    assert (pool, crush, weights) == (POOL, by_hand, [0x10000] * 12)


@pytest.mark.parametrize("what,parts", [
    ("as_configured", []),
    ("two_osds_under_one_host", ["buckets"]),
    ("failure_domain_osd", ["rule"]),
    ("size_2", ["pool"]),
    ("an_osd_out", ["weights"])])
def test_a_wrongly_built_map_is_misplaced(what, parts):
    ctx, driver = control._context(CELL, 7, rehearsal=True)
    p, pool, name, answers, at_ack, placed = _sound(ctx, driver)
    want = driver.expected(dict(ctx.config, osds=12, pg_num=32), 1)
    gp, gc, gw = json.loads(json.dumps(want))      # the cluster's, a copy
    if what == "two_osds_under_one_host":
        gc["buckets"][1]["items"] = [0, 1]
        gc["buckets"][1]["weights"] = [0x10000] * 2
        del gc["buckets"][2]
        gc["buckets"][0]["items"].remove(-3)
    elif what == "failure_domain_osd":
        gc["rule"][1] = ["chooseleaf_firstn", 0, 0]
    elif what == "size_2":
        gp["size"] = 2
    elif what == "an_osd_out":
        gw[5] = 0
    differs = driver.parts_differing(want, (gp, gc, gw))
    assert differs == parts
    driver.compare(ctx, p, pool, name, answers, at_ack, placed, differs)
    assert ctx.compared.rows["replicas_misplaced"]["value"] == len(parts)
    assert ctx.compared.ok is (not parts)


@pytest.mark.parametrize("script,fault,numbers", [
    # (a rehearsal compares 4 objects, so replicas_missing may miss the
    # one store that changes nothing; the 100-odd acks do not)
    ("faulty_run.py", "write_state_unchanged", {"replicas_missing_at_ack"}),
    ("faulty_run_rep.py", "replica_acks_before_commit",
     {"replicas_missing_at_ack"}),
    ("faulty_run_rep.py", "replica_bytes_altered", {"replicas_differing"}),
], ids=lambda v: str(v))
def test_a_fault_in_the_timed_path_is_not_correct(script, fault, numbers):
    proc = run([fault, "--workload", CELL, "--seed", "77", "--seconds", "2",
                "--trace", "0"], devices=CELLS[CELL]["chips"],
               script=f"benchmark/tests/{script}")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    over = {n for n, r in line["compared"].items()
            if r["value"] is None or r["value"] > r["limit"]}
    assert numbers <= over, line["compared"]


def _reader(name):
    return runner._load_py(runner.BENCH / "layer_metrics" / f"{name}.py")


def test_fanout_bytes_per_op_reads_the_drivers_deltas():
    read = _reader("rep_fanout_bytes_per_op").read
    ctx = types.SimpleNamespace(obs={"rep_ops": 4,
                                     "rep_fanout_bytes": 4 * 8388608})
    assert read(ctx) == 8388608
    assert read(types.SimpleNamespace(obs={"rep_ops": 0})) is None
    assert read(types.SimpleNamespace(obs={})) is None     # a parent commit


def test_rep_wait_reads_the_ops_wholly_inside_the_stretch(monkeypatch):
    from ceph_tpu.utils import tracing
    read = _reader("rep_wait_ms").read
    ms = 1_000_000

    def rec(kind, name, t0, t1, trace, parent=0):
        return (kind, name, "osd.1", 0, t0 * ms, t1 * ms, trace, 9, parent)
    recs = [
        rec("interval", "client_op", 1100, 1900, 11),
        rec("interval", "rep_subop_wait", 1200, 1500, 11, 5),
        rec("interval", "client_op", 1050, 1700, 12),
        rec("interval", "rep_subop_wait", 1100, 1200, 12, 5),
        rec("interval", "rep_subop_wait", 1300, 1400, 12, 5),  # a resend
        rec("interval", "client_op", 900, 1500, 13),           # began before
        rec("interval", "rep_subop_wait", 1000, 1400, 13, 5),
        rec("interval", "ec_subop_wait", 1200, 1900, 11, 5),
    ]
    monkeypatch.setattr(tracing, "captured", lambda: recs)
    ctx = types.SimpleNamespace(trace_span=(1.0, 2.0))
    assert read(ctx, "repop") == 200.0       # ops 11 (300) and 12 (200)
    assert read(ctx, "other") is None
    assert read(types.SimpleNamespace(trace_span=None), "repop") is None
    monkeypatch.setattr(tracing, "captured", lambda: [recs[0], recs[7]])
    assert read(ctx, "repop") is None        # a program without the span

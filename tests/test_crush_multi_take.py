"""A rule of several take/emit blocks on the fused kernel: one kernel
plan a block, the blocks' columns merged as firstn EMIT keeps the first
``result_max`` (the docs' ``mixed_replicated_rule``, SSD primary and HDD
replicas; ref: mapper.c crush_do_rule, Ceph docs, CRUSH Maps).

The kernel runs in Pallas interpret mode on the map the class sweep's
driver builds (``drivers/crush_sweep_classes.build_program_map``) at the
configuration's rehearsal size, 256 OSDs under 16 hosts of 12 hdd and 4
ssd. Every case is held lane for lane against the rule VM (the same
map, the kernel off) and against the benchmark's plain reference
(``reference/crush_class_ref.py``), which imports nothing of the
program."""

import json
import pathlib
import sys

import numpy as np
import pytest

from ceph_tpu.crush import mapper as mapper_mod
from ceph_tpu.crush import pallas_mapper as pm
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.sharded_sweep import sharded_map_pgs, sharded_sweep
from ceph_tpu.crush.tensors import pack_map
from ceph_tpu.crush.types import WEIGHT_ONE
from ceph_tpu.parallel import local_mesh

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from drivers import crush_sweep_classes as drv            # noqa: E402
from reference import crush_class_ref as cr               # noqa: E402

CFG = json.loads((BENCH / "configs" / "crush-10k-hybrid-classes.json")
                 .read_text())
DESC = {**CFG["map"], **CFG["rehearsal"]["map"]}
N = 4096                                  # the rehearsal's sweep


def _rule(rid: int, *blocks: str) -> str:
    body = "".join(f"\tstep take root class {c}\n"
                   f"\tstep chooseleaf firstn {k} type {t}\n\tstep emit\n"
                   for c, k, t in (b.split() for b in blocks))
    return f"rule r{rid} {{\n\tid {rid}\n\ttype replicated\n{body}}}\n"


DOCS = 1                                  # the configuration's own rule
SSD, HDD = 2, 3                           # its two blocks as rules of one
THREE = 4                                 # ssd host, hdd rack, hdd hosts
REFUSED = 5                               # a two-step block after the ssd one
RULES = {SSD: _rule(SSD, "ssd 1 host"), HDD: _rule(HDD, "hdd 0 host"),
         THREE: _rule(THREE, "ssd 1 host", "hdd 1 rack", "hdd 0 host")}
REFUSED_TEXT = f"""rule r{REFUSED} {{
\tid {REFUSED}
\ttype replicated
\tstep take root class ssd
\tstep chooseleaf firstn 1 type host
\tstep emit
\tstep take root class hdd
\tstep choose firstn 2 type rack
\tstep chooseleaf firstn 1 type host
\tstep emit
}}
"""


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "interpret")


@pytest.fixture(scope="module")
def hybrid():
    """The program's map (every rule above in its text) and one
    reference a rule."""
    refs = {DOCS: cr.ClassReference(DESC, CFG["classes"],
                                    CFG["rule_text"], 0)}
    for rid, text in RULES.items():
        refs[rid] = cr.ClassReference(DESC, CFG["classes"], text, 0)
    text = CFG["rule_text"] + "".join(RULES.values()) + REFUSED_TEXT
    cmap, _args = drv.build_program_map(DESC, text, refs[DOCS])
    drv.same_tree_and_rule(cmap, refs[DOCS], DOCS)
    return cmap, refs


def _mappers(cmap, monkeypatch, weights=None):
    """(kernel, rule VM) Mappers of one map."""
    kern = Mapper(cmap, weights)
    monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "0")
    vm = Mapper(cmap, weights)
    monkeypatch.setenv("CEPH_TPU_CRUSH_KERNEL", "interpret")
    assert kern._kernel_mode == "interpret" and vm._kernel_mode is None
    return kern, vm


@pytest.mark.parametrize("rid,width", [(DOCS, 3), (THREE, 3), (THREE, 4)],
                         ids=["docs-rule", "three-blocks", "three-blocks-4"])
def test_several_blocks_run_a_plan_each_lane_for_lane(hybrid, monkeypatch,
                                                      rid, width):
    """One plan a block, the kernel path promised, and every lane's
    vector the rule VM's and the reference's, position by position."""
    cmap, refs = hybrid
    kern, vm = _mappers(cmap, monkeypatch)
    plans = kern._kernel_plan(rid)
    assert isinstance(plans, tuple) and len(plans) == kern.takes(rid)
    assert kern.mapping_path(rid, width) == "pallas-interpret"
    assert vm.mapping_path(rid, width) == "xla"
    info = kern.kernel_plan_info(rid, width)
    assert info["take_plans"] == len(plans)
    assert len(info["kernel_lanes"]) == len(plans)
    xs = np.arange(N, dtype=np.uint32)
    got = np.asarray(kern.map_pgs(rid, xs, width))
    assert (got == np.asarray(vm.map_pgs(rid, xs, width))).all()
    assert (got == cr.map_batch(refs[rid].map, refs[rid].steps, xs,
                                width)).all()
    ssd = [refs[DOCS].klass[d] == "ssd" for d in got[:, 0]]
    assert all(ssd)


def test_every_ssd_out_emits_the_third_hdd(hybrid, monkeypatch):
    """Every SSD marked out: the ssd block places nothing (every lane
    flags to its recompute, which finds nothing either), so the hdd
    block's third pick is emitted: the vectors are the hdd rule's."""
    cmap, refs = hybrid
    klass = refs[DOCS].klass
    weights = np.array([0 if c == "ssd" else WEIGHT_ONE for c in klass],
                       dtype=np.int64)
    kern, vm = _mappers(cmap, monkeypatch, weights)
    assert kern.mapping_path(DOCS, 3) == "pallas-interpret"
    xs = np.arange(N, dtype=np.uint32)
    got = np.asarray(kern.map_pgs(DOCS, xs, 3))
    assert (got == np.asarray(vm.map_pgs(DOCS, xs, 3))).all()
    assert (got == cr.map_batch(refs[HDD].map, refs[HDD].steps, xs,
                                3)).all()
    assert all(klass[d] == "hdd" for d in got.ravel())


def test_a_sweep_counts_two_take_plans_a_block(hybrid, monkeypatch):
    """``crushtool --test``'s sweep of the docs' rule: the reference's
    counts, and ``kernel_take_plans`` two for every block swept; a
    rule of one block counts one."""
    cmap, refs = hybrid
    kern, _vm = _mappers(cmap, monkeypatch)
    (want, want_bad), = refs[DOCS].counts([(1, N)], 3)
    for rid, plans in ((DOCS, 2), (HDD, 1)):
        before = mapper_mod.PERF.dump()
        counts, bad, path = kern.sweep_path(rid, 1, N, 3)
        after = mapper_mod.PERF.dump()
        blocks = after["sweep_blocks"] - before["sweep_blocks"]
        assert path == "pallas-interpret" and blocks == 1
        assert after["kernel_take_plans"] - before["kernel_take_plans"] \
            == plans * blocks
        if rid == DOCS:
            assert (np.asarray(counts) == want).all()
            assert int(bad) == want_bad == 0


def test_a_rule_of_one_block_keeps_its_plan(hybrid):
    """A rule of one block gets one ``KernelPlan``, not a tuple, and it
    is, field by field, the plan its block gets inside the docs' rule
    (one plan a block, built by the one function)."""
    cmap, _refs = hybrid
    docs = pm.build_plan(cmap, pack_map(cmap), DOCS)
    for rid, inside in ((SSD, docs[0]), (HDD, docs[1])):
        plan = pm.build_plan(cmap, pack_map(cmap), rid)
        assert isinstance(plan, pm.KernelPlan)
        for f in pm.KernelPlan.__dataclass_fields__:
            a, b = getattr(plan, f), getattr(inside, f)
            if isinstance(a, tuple) and a and isinstance(a[0], np.ndarray):
                assert all(np.array_equal(x, y) for x, y in zip(a, b)), f
            elif isinstance(a, np.ndarray) or a is None:
                assert (a is None and b is None) or np.array_equal(a, b), f
            else:
                assert a == b, f
    assert docs[0].numrep_arg == 1 and docs[1].numrep_arg == 0


def test_a_refused_block_keeps_the_whole_rule_on_the_rule_vm(
        hybrid, monkeypatch):
    """A two-step second block (``choose firstn 2 type rack`` then
    ``chooseleaf``) is no kernel plan, so neither is the rule: the XLA
    path serves it, as it serves the rule VM."""
    cmap, _refs = hybrid
    kern, vm = _mappers(cmap, monkeypatch)
    assert pm.build_plan(cmap, pack_map(cmap), REFUSED) is None
    assert kern.mapping_path(REFUSED, 3) == "xla"
    assert kern.kernel_plan_info(REFUSED, 3) is None
    xs = np.arange(512, dtype=np.uint32)
    assert (np.asarray(kern.map_pgs(REFUSED, xs, 3))
            == np.asarray(vm.map_pgs(REFUSED, xs, 3))).all()


def test_the_mesh_serves_the_same_plans(hybrid, monkeypatch):
    """``sharded_map_pgs`` and ``sharded_sweep`` over two devices run
    the single-device path's body: the same vectors and counts."""
    cmap, refs = hybrid
    mesh = local_mesh(2)
    kern, _vm = _mappers(cmap, monkeypatch)
    xs = np.arange(1, 1 + N, dtype=np.uint32)
    want = np.asarray(kern.map_pgs(DOCS, xs, 3))
    assert (np.asarray(sharded_map_pgs(mesh, kern, DOCS, xs, 3))
            == want).all()
    counts, bad = sharded_sweep(mesh, kern, DOCS, 1, N, 3)
    assert (np.asarray(counts) == np.bincount(want.ravel(),
                                              minlength=256)).all()
    assert int(bad) == 0
    assert kern.last_map_path == "pallas-interpret+sharded"

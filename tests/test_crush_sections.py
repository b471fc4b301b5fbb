"""The placement path's tracing sections (``crush.*``): the tree a sweep
records through ``CrushTester.test``, ``Mapper.sweep_path`` and
``sharded_sweep``, their tags, the kernel path's forced read and its
failure retry, and the off path (no profiler session: one shared
``_OFF``, no tag computed). Small maps and 64-lane blocks: the programs
compile in seconds on the CPU."""

import numpy as np
import pytest

from ceph_tpu.crush import builder, mapper as mapper_mod
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.sharded_sweep import sharded_sweep
from ceph_tpu.crush.tester import CrushTester
from ceph_tpu.parallel import local_mesh
from ceph_tpu.utils import tracing


@pytest.fixture
def roots(monkeypatch):
    """Every section opened from here on, as a profiler session keeps
    it (tags and stamps), in a tree of what opened inside what."""
    top, stack = [], []

    class Rec(tracing.Span):
        __slots__ = ("kids",)

        def __enter__(self):
            self.kids = []
            (stack[-1].kids if stack else top).append(self)
            stack.append(self)
            return self

        def __exit__(self, *exc):
            stack.pop()
            self.finish()

    def section(name, ctx=None, tracer=None, service=""):
        return Rec(tracer, name, 0, 0, None, tracing.SECTION, service)
    monkeypatch.setattr(tracing, "section", section)
    return top


def shape(secs):
    """The tree as nested (name, [children]), every section closed."""
    assert all(s.finished for s in secs)
    return [(s.name, shape(s.kids)) for s in secs]


@pytest.fixture(scope="module")
def firstn():
    m, root = builder.build_hierarchy(6, 2)
    return m, builder.add_simple_rule(m, root, builder.TYPE_HOST)


def test_a_firstn_sweep_records_test_sweep_dispatch_readback(firstn, roots):
    m, rid = firstn
    res = CrushTester(m, batch=64).test(rid, 3, 0, 99)
    assert res.device_counts.sum() == 300
    # the XLA path forces no block: its firstn tally's read is the
    # sweep's one sync, and the tester's reads find the counts home
    assert shape(roots) == [("crush.test", [
        ("crush.sweep", [("crush.dispatch", []), ("crush.dispatch", []),
                         ("crush.readback", [])]),
        ("crush.readback", [])])]
    sweep = roots[0].kids[0]
    assert sweep.tags == {"lanes": 100, "takes": 1, "blocks": 2,
                          "width": 64}
    assert [d.tags for d in sweep.kids] == [{"block": 0}, {"block": 1}, {}]
    assert sweep.service == "crush"


def test_an_indep_sweep_reads_its_tally_inside_the_sweep(roots):
    m, root = builder.build_hierarchy(6, 2)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
    CrushTester(m, batch=64).test(rid, 4, 0, 63)
    assert shape(roots) == [("crush.test", [
        ("crush.sweep", [("crush.dispatch", []), ("crush.readback", [])]),
        ("crush.readback", [])])]
    assert roots[0].kids[0].tags == {"lanes": 64, "takes": 1, "blocks": 1,
                                     "width": 64, "narrow_width": 0}


def test_keep_mappings_maps_without_a_sweep_section(firstn, roots):
    m, rid = firstn
    res = CrushTester(m, batch=64).test(rid, 3, 0, 63, keep_mappings=True)
    assert res.mappings.shape == (64, 3)
    # map_pgs records nothing: the served path's placements neither
    assert shape(roots) == [("crush.test", [("crush.readback", [])])]


def _fake_kernel(mp, body):
    """Route the sweep down the kernel's branch with ``body`` as the
    kernel (the quarantine tests' stand-in, tests/test_devmon.py): it
    forces its first block, and a failure degrades to the XLA path."""
    mp._kernel_plan = lambda ruleno: None
    mp._kernel_body = lambda ruleno, result_max, tally=False: (
        body if mp._kernel_mode is not None else None)
    mp._kernel_mode = "interpret"


def test_the_kernel_path_forces_its_first_block(firstn, roots,
                                                monkeypatch):
    monkeypatch.setattr(mapper_mod, "MIN_BLOCK_WIDTH", 64)
    m, rid = firstn
    mp = Mapper(m, block=64)
    _fake_kernel(mp, mp._rule_fn(rid, 3))
    counts, bad, path = mp.sweep_path(rid, 0, 64, 3)
    assert path == "pallas-interpret" and int(np.asarray(counts).sum()) \
        == 192
    assert shape(roots) == [("crush.sweep", [("crush.dispatch", []),
                                             ("crush.force", [])])]


def test_a_kernel_failure_retries_inside_the_failed_sweep(firstn, roots,
                                                          monkeypatch):
    monkeypatch.setattr(mapper_mod, "MIN_BLOCK_WIDTH", 64)
    m, rid = firstn
    mp = Mapper(m, block=64)

    def broken(arrs, xs):
        raise RuntimeError("injected kernel failure")
    _fake_kernel(mp, broken)
    counts, bad, path = mp.sweep_path(rid, 0, 64, 3)
    assert path == "xla" and int(np.asarray(counts).sum()) == 192
    # the failed dispatch closed on the way out; the retry is a sweep
    # of its own inside the failed one
    assert shape(roots) == [("crush.sweep", [
        ("crush.dispatch", []),
        ("crush.sweep", [("crush.dispatch", []), ("crush.readback", [])])])]
    assert "blocks" not in roots[0].tags
    assert roots[0].kids[1].tags == {"lanes": 64, "takes": 1, "blocks": 1,
                                     "width": 64}


def test_the_sharded_sweep_on_the_virtual_mesh(firstn, roots):
    m, rid = firstn
    mesh = local_mesh()
    mp = Mapper(m, block=64, mesh=mesh, mesh_min_batch=64)
    n = 8 * 64
    counts, bad = sharded_sweep(mesh, mp, rid, 0, n, 3)
    assert int(np.asarray(counts).sum()) == 3 * n
    assert shape(roots) == [("crush.sweep", [("crush.dispatch", [])])]
    assert roots[0].tags == {"lanes": n, "takes": 1, "blocks": 1,
                             "width": 64}
    assert roots[0].kids[0].tags == {"block": 0}
    roots.clear()
    # through the Mapper: its sweep holds the module's
    _c, _b, path = mp.sweep_path(rid, 0, n, 3)
    assert path == "xla+sharded"
    assert shape(roots) == [("crush.sweep", [
        ("crush.sweep", [("crush.dispatch", [])])])]
    assert roots[0].tags == {"lanes": n, "takes": 1}


def test_with_no_session_a_section_is_the_shared_off_and_tags_nothing(
        firstn, monkeypatch):
    assert not tracing.capturing()
    assert tracing.section("crush.sweep", service="crush") is tracing._OFF

    class Strict(type(tracing._OFF)):
        __slots__ = ()

        def tag(self, key, value):
            raise AssertionError(f"tag {key} computed with no session")
    monkeypatch.setattr(tracing, "_OFF", Strict())
    m, rid = firstn
    CrushTester(m, batch=64).test(rid, 3, 0, 99)
    mesh = local_mesh()
    sharded_sweep(mesh, Mapper(m, block=64), rid, 0, 8 * 64, 3)

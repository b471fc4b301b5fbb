"""Device classes as a cluster's own map carries them: every bucket's
class shadows under the ids its ``crushtool -d`` text states
(``id <n> class <c>``), and the docs' hybrid rule (SSD primary, HDD
replicas: two take/emit blocks) on the rule VM against the scalar spec,
with the firstn tally a rule VM sweep carries (ref: Ceph docs, CRUSH
Maps, device classes and ``mixed_replicated_rule``;
CrushCompiler.cc, CrushWrapper::device_class_clone)."""

import numpy as np
import pytest

from ceph_tpu.bench import crushtool
from ceph_tpu.crush import mapper as mapper_mod
from ceph_tpu.crush import mapper_ref
from ceph_tpu.crush.compiler import (CompileError, compile_crushmap,
                                     decompile_crushmap)
from ceph_tpu.crush.mapper import Mapper
from ceph_tpu.crush.tester import CrushTester
from ceph_tpu.crush.types import ITEM_NONE

OSDS, HOSTS, RACKS = 256, 16, 4
PER_HOST = OSDS // HOSTS


def klass(osd: int) -> str:
    """12 hdd and 4 ssd in every host of 16."""
    return "hdd" if osd % PER_HOST < 12 else "ssd"


HDD_RULE = """rule hdd_rule {
\tid 1
\ttype replicated
\tstep take root class hdd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
"""
SSD_RULE = """rule ssd_rule {
\tid 2
\ttype replicated
\tstep take root class ssd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
"""
# Ceph docs, CRUSH Maps: the primary on an SSD, the replicas on HDDs
HYBRID_RULE = """rule mixed_replicated_rule {
\tid 3
\ttype replicated
\tstep take root class ssd
\tstep chooseleaf firstn 1 type host
\tstep emit
\tstep take root class hdd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
"""


def _built():
    args = crushtool.parse_args(
        ["--build", "--num-osds", str(OSDS), "--hosts", str(HOSTS),
         "--racks", str(RACKS), "--alg", "straw2"])
    return crushtool.build_map(args)


def shadow_ids(m, order=("hdd", "ssd")) -> dict:
    """(bucket id, class) -> its shadow's id: each class's tree depth
    first, children before their parent, each id the next below the
    lowest in use."""
    ids, low = {}, min(m.buckets)
    root = min(m.buckets)

    def walk(bid, c):
        nonlocal low
        for item in m.buckets[bid].items:
            if item < 0:
                walk(item, c)
        low -= 1
        ids[(bid, c)] = low
    for c in order:
        walk(root, c)
    return ids


def class_text(rules=(), stated=None) -> str:
    """The built map's ``-d`` text with every OSD's class and, where
    ``stated`` maps (bucket id, class) to an id, that bucket's shadow
    ids as ``id <n> class <c>`` lines; the rules appended."""
    m = _built()
    out = []
    for line in decompile_crushmap(m).splitlines():
        tok = line.split()
        if tok[:1] == ["device"]:
            line += f" class {klass(int(tok[1]))}"
        out.append(line)
        if stated and tok[:1] == ["id"] and len(tok) == 2:
            for (bid, c), sid in sorted(stated.items()):
                if bid == int(tok[1]):
                    out.append(f"\tid {sid} class {c}")
    return "\n".join(out) + "\n" + "".join(rules)


def shadows(m) -> dict:
    """name -> (id, items, weights) of every class shadow."""
    return {name: (bid, list(m.buckets[bid].items),
                   list(m.buckets[bid].weights))
            for bid, name in m.bucket_names.items() if "~" in name}


@pytest.fixture(scope="module")
def stated():
    return shadow_ids(_built())


def test_stated_shadow_ids_are_honoured(stated):
    """Every shadow takes the id its line states, including those no
    rule takes, and holds the shadows of its children and its own
    class's devices."""
    # the ssd tree stated first: not the order a rule would make them in
    ids = shadow_ids(_built(), order=("ssd", "hdd"))
    m = compile_crushmap(class_text([HDD_RULE], ids))
    names = {bid: name for bid, name in m.bucket_names.items()}
    assert len(shadows(m)) == 2 * len({b for b, _c in ids})
    for (bid, c), sid in ids.items():
        assert names[sid] == f"{names[bid]}~{c}"
        want = [ids[(i, c)] if i < 0 else i for i in m.buckets[bid].items
                if i < 0 or klass(i) == c]
        assert m.buckets[sid].items == want
    assert m.rules[1].steps[0].arg1 == ids[(min(_built().buckets), "hdd")]


@pytest.mark.parametrize("text_states", [True, False],
                         ids=["ids-stated", "ids-unstated"])
def test_decompile_then_compile_keeps_every_shadow_id_and_mapping(
        stated, text_states):
    """``-d`` writes each shadow's ``id <n> class <c>`` line, so ``-c``
    of that text keeps every shadow, and every placement, whether the
    first text stated the ids or the rules made them."""
    m1 = compile_crushmap(class_text([SSD_RULE, HDD_RULE, HYBRID_RULE],
                                     stated if text_states else None))
    text = decompile_crushmap(m1)
    assert sum(" class " in ln and ln.split()[0] == "id"
               for ln in text.splitlines()) == len(shadows(m1)) > 0
    m2 = compile_crushmap(text)
    assert shadows(m2) == shadows(m1)
    assert decompile_crushmap(m2) == text
    xs = np.arange(1000, 1512, dtype=np.uint32)
    for rule in (1, 2, 3):
        a = np.asarray(Mapper(m1).map_pgs(rule, xs, 3))
        b = np.asarray(Mapper(m2).map_pgs(rule, xs, 3))
        assert (a == b).all(), rule


def test_a_map_without_classes_decompiles_as_it_did():
    text = decompile_crushmap(_built())
    assert not any(" class " in ln for ln in text.splitlines())
    assert decompile_crushmap(compile_crushmap(text)) == text


@pytest.mark.parametrize("text_states,differ", [(True, 0), (False, 2000)],
                         ids=["ids-stated", "ids-unstated"])
def test_the_order_of_the_rules_moves_no_mapping(stated, text_states,
                                                 differ):
    """An ssd rule before or after an hdd rule in the text: with the
    ids stated, the hdd rule's 2,000 mappings are the same; a text
    that states none still gets its shadows in the order its rules take
    them, as it always did, and the hdd rule's placement moves."""
    ids = stated if text_states else None
    first = compile_crushmap(class_text([HDD_RULE, SSD_RULE], ids))
    second = compile_crushmap(class_text([SSD_RULE, HDD_RULE], ids))
    assert (shadows(first) == shadows(second)) == text_states
    xs = np.arange(2000, dtype=np.uint32)
    a = np.asarray(Mapper(first).map_pgs(1, xs, 3))
    b = np.asarray(Mapper(second).map_pgs(1, xs, 3))
    assert int((a != b).any(axis=1).sum()) == differ


def test_a_stated_id_that_is_taken_is_a_compile_error(stated):
    host, root = max(_built().buckets), min(_built().buckets)
    taken = dict(stated)
    taken[(host, "hdd")] = root                       # a real bucket's
    with pytest.raises(CompileError, match="taken"):
        compile_crushmap(class_text((), taken))
    twice = dict(stated)
    twice[(host, "hdd")] = twice[(host, "ssd")]       # another shadow's
    with pytest.raises(CompileError, match="taken"):
        compile_crushmap(class_text((), twice))
    text = class_text((), stated).replace(
        f"\tid {stated[(host, 'hdd')]} class hdd",
        f"\tid {stated[(host, 'hdd')]} class hdd\n\tid -9999 class hdd")
    with pytest.raises(CompileError, match="twice"):
        compile_crushmap(text)


@pytest.fixture(scope="module")
def hybrid(stated):
    return compile_crushmap(class_text([HYBRID_RULE], stated))


def test_the_hybrid_rule_places_an_ssd_primary_and_two_hdd_hosts(hybrid):
    """Through ``CrushTester.test`` (the sweep's counts, and the kept
    mappings) and ``Mapper.map_pgs``, against ``mapper_ref``: position
    0 an ssd, positions 1-2 hdds on two hosts (the ssd's host may hold
    one of them: the blocks do not see each other's picks); block 2
    chooses three HDD hosts and EMIT keeps the two that fit."""
    m, n = hybrid, 512
    tester = CrushTester(m, batch=n)
    swept = tester.test(3, 3, 0, n - 1)
    kept = tester.test(3, 3, 0, n - 1, keep_mappings=True)
    got = np.asarray(Mapper(m).map_pgs(3, np.arange(n), 3))
    assert (kept.mappings == got).all() and swept.path == "xla"
    assert swept.bad_mappings == kept.bad_mappings == 0
    assert (swept.device_counts == np.bincount(
        got.ravel(), minlength=OSDS)).all()
    shared = 0
    for x, row in enumerate(got.tolist()):
        assert mapper_ref.do_rule(m, 3, x, 3) == row
        assert [klass(d) for d in row] == ["ssd", "hdd", "hdd"]
        hosts = [d // PER_HOST for d in row]
        assert hosts[1] != hosts[2]
        shared += hosts[0] in hosts[1:]
    assert 0 < shared < n // 4
    # the third HDD the second block chose is not emitted
    assert len(mapper_ref.do_rule(m, 3, 7, 3)) == 3
    assert len(mapper_ref.do_rule(m, 3, 7, 4)) == 4


# -- the firstn tally -------------------------------------------------------

def _spec_slots(m, ruleno, x, result_max):
    """mapper.c's firstn (stable, descend_once, all devices in) one
    slot and one try at a time: per take/emit block, per slot, the try
    that placed it (None: none of ``tries`` did), and the result."""
    tries = m.tunables.choose_total_tries
    result, blocks = [], []
    for s in m.rules[ruleno].steps:
        if s.op == mapper_mod.OP_TAKE:
            root = s.arg1
        elif s.op == mapper_mod.OP_CHOOSELEAF_FIRSTN:
            numrep = s.arg1 if s.arg1 > 0 else s.arg1 + result_max
            items, leaves, slots = [], [], []
            for rep in range(numrep):
                placed = None
                for t in range(tries):
                    r, item = rep + t, root
                    while item < 0 and m.buckets[item].type != s.arg2:
                        item = mapper_ref.bucket_straw2_choose(
                            m.buckets[item], x, r)
                    leaf = item
                    while leaf < 0:
                        leaf = mapper_ref.bucket_straw2_choose(
                            m.buckets[leaf], x, r)
                    if item not in items and leaf not in leaves:
                        placed = t
                        items.append(item)
                        leaves.append(leaf)
                        break
                slots.append(placed)
            blocks.append(slots)
            result += leaves[:result_max - len(result)]
    return blocks, result


def _collide_map():
    """Five hosts of two OSDs, three replicas over hosts: a slot
    collides on its first two tries often enough to need the loop."""
    args = crushtool.parse_args(["--build", "--num-osds", "10", "--hosts",
                                 "5", "--alg", "straw2"])
    return crushtool.build_map(args), 0, 4


@pytest.mark.parametrize("case", ["collide", "hybrid"])
def test_the_firstn_tally_counts_the_loop(case, hybrid):
    """``firstn_slots`` is the lanes x the slots of every firstn block;
    ``firstn_loop_lanes`` the lane-slots whose first two tries (the
    speculative ones) both failed; ``firstn_loop_rounds`` the rounds
    the loop ran, a slot's being its unluckiest lane's, summed over
    the blocks' slots: each against the scalar spec, one try at a
    time."""
    m, rule, width = _collide_map() if case == "collide" else (hybrid, 3, 3)
    n, tries = 512, m.tunables.choose_total_tries
    slots = lanes = 0
    rounds = None
    for x in range(n):
        blocks, result = _spec_slots(m, rule, x, width)
        assert result == mapper_ref.do_rule(m, rule, x, width)
        if rounds is None:
            rounds = [[0] * len(b) for b in blocks]
        for bi, block in enumerate(blocks):
            slots += len(block)
            for si, t in enumerate(block):
                if t is None or t >= 2:
                    lanes += 1
                    rounds[bi][si] = max(rounds[bi][si], tries - 2
                                         if t is None else t - 1)
    before = mapper_mod.PERF.dump()
    counts, bad, path = Mapper(m, block=n).sweep_path(rule, 0, n, width)
    after = mapper_mod.PERF.dump()
    delta = {k: after[k] - before[k] for k in mapper_mod.FIRSTN_TALLY}
    assert path == "xla" and after["sweep_blocks"] - before[
        "sweep_blocks"] == 1
    assert delta == {"firstn_slots": slots, "firstn_loop_lanes": lanes,
                     "firstn_loop_rounds": sum(map(sum, rounds))}
    assert lanes > 0 and delta["firstn_loop_rounds"] > 0
    assert int(np.asarray(counts).sum()) == n * width and int(bad) == 0


def test_the_tally_leaves_the_other_sweeps_as_they_were(hybrid):
    """An indep sweep moves no firstn counter, and the rule VM's
    program without the tally is the one it was: the kernel's
    recompute and the map_pgs path ask for none."""
    from ceph_tpu.crush import builder
    m, root = builder.build_hierarchy(6, 2)
    rid = builder.add_simple_rule(m, root, builder.TYPE_HOST, indep=True)
    before = mapper_mod.PERF.dump()
    CrushTester(m, batch=64).test(rid, 4, 0, 63)
    after = mapper_mod.PERF.dump()
    assert all(after[k] == before[k] for k in mapper_mod.FIRSTN_TALLY)
    mp = Mapper(hybrid)
    key = mp._rule_key(3, 3)
    plain = mapper_mod._rule_body(*key)
    tallied = mapper_mod._rule_body(*key, tally=mapper_mod.FIRSTN_TALLY)
    xs = np.arange(64, dtype=np.uint32)
    import jax
    w, stats = jax.jit(tallied)(mp.arrays, xs)
    assert (np.asarray(jax.jit(plain)(mp.arrays, xs)) == np.asarray(w)).all()
    assert np.asarray(stats).tolist()[0] == 64 * 4
    assert ITEM_NONE not in np.asarray(w)

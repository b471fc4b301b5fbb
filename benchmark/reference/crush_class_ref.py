"""Plain reference for placement by device class: ``crush_do_rule``
for rules of one or more ``take <root> class <c>`` / ``chooseleaf
firstn`` / ``emit`` blocks on the class shadow trees, written from
src/crush/mapper.c (``crush_do_rule``: EMIT keeps what fits under
``result_max``) and CrushWrapper.cc (``device_class_clone``: a bucket's
shadow keeps the devices of the class and the shadows of its children,
its weights the sum below it).

It imports nothing of the program. The tree is ``crush_ref.build_map``'s;
the classes and the shadows' ids come from the configuration file's
``classes``: in every host the first ``hdd`` OSDs are hdd and the next
``ssd`` ssd (``per_host``), and each class's shadow tree takes its ids
depth first, children before their parent, the classes in ``order``,
each id the next below the lowest in use. A shadow with nothing of its
class below it is left out of its parent (and on this map there is
none). The hash, ``crush_ln``, the straw2 draw and the firstn loop are
``crush_ref``'s; only the blocks and EMIT are this file's.

Two forms of the same semantics, as in ``crush_ref``: ``do_rule`` one
input at a time, ``map_batch`` over an array in numpy, lane for lane
identical (benchmark/tests hold them equal).
"""

from __future__ import annotations

import numpy as np

from reference import crush_ref
from reference.crush_ref import ITEM_NONE, Bucket, Map

TYPES = {"osd": 0, "host": 1, "rack": 3, "root": 10}


def class_of(desc: dict, classes: dict) -> list[str]:
    """Every OSD's class: position ``o % per_host`` in its host, the
    classes of ``order`` in turn, ``classes[c]`` OSDs each."""
    per = int(desc["osds"]) // int(desc["hosts"])
    ladder = [c for c in classes["order"] for _ in range(int(classes[c]))]
    if len(ladder) != per:
        raise ValueError(f"the classes cover {len(ladder)} OSDs of a "
                         f"host of {per}")
    return [ladder[o % per] for o in range(int(desc["osds"]))]


def shadow_trees(m: Map, klass: list[str], order) -> dict:
    """(bucket id, class) -> the shadow ``Bucket``, ids by the rule
    above; every bucket of ``m`` hangs under its one root."""
    root, = [b.id for b in m.buckets.values() if b.type == TYPES["root"]]
    low, out = min(m.buckets), {}

    def clone(bid, c):
        nonlocal low
        items, weights = [], []
        for item, w in zip(m.buckets[bid].items, m.buckets[bid].weights):
            if item >= 0:
                if klass[item] == c:
                    items.append(item)
                    weights.append(w)
            else:
                sub = clone(item, c)
                if sub.items:
                    items.append(sub.id)
                    weights.append(sum(sub.weights))
        low -= 1
        out[(bid, c)] = Bucket(low, m.buckets[bid].type, items, weights)
        return out[(bid, c)]
    for c in order:
        clone(root, c)
    return out


def with_shadows(m: Map, shadows: dict) -> Map:
    """The tree and its shadows in one map (the program's map holds
    both; placement only ever walks one of them)."""
    return Map(list(m.buckets.values()) + list(shadows.values()),
               m.max_devices, m.rule, m.tunables)


def parse_rule(text: str, m: Map, shadows: dict) -> list[tuple]:
    """The ``step`` lines of a rule -> [(name, arg, ...)]; ``take root
    class <c>`` names the root's shadow by its id."""
    root, = [b.id for b in m.buckets.values() if b.type == TYPES["root"]]
    steps = []
    for line in text.splitlines():
        tok = line.split("#")[0].split()
        if not tok or tok[0] != "step":
            continue
        if tok[1] == "take":
            if tok[2] != "root":
                raise ValueError(f"the reference takes 'root': {line!r}")
            steps.append(("take", shadows[(root, tok[4])].id
                          if tok[3:4] == ["class"] else root))
        elif tok[1] == "chooseleaf" and tok[2] == "firstn" \
                and tok[4] == "type":
            steps.append(("chooseleaf_firstn", int(tok[3]), TYPES[tok[5]]))
        elif tok[1] == "emit":
            steps.append(("emit",))
        else:
            raise ValueError(f"rule step not in the reference: {line!r}")
    return steps


def step_codes(steps) -> list[tuple[int, int, int]]:
    """crush.h's (op, arg1, arg2) of the steps: take 1, chooseleaf
    firstn 6, emit 4."""
    op = {"take": 1, "chooseleaf_firstn": 6, "emit": 4}
    return [(op[s[0]], *(list(s[1:]) + [0, 0])[:2]) for s in steps]


# -- one input at a time ----------------------------------------------------

def do_rule(m: Map, steps, x: int, result_max: int, ln: str = "exact",
            truncate: bool = True) -> list[int]:
    """mapper.c crush_do_rule for blocks of take / chooseleaf firstn /
    emit, every device in. ``truncate`` False is a control: EMIT keeps
    every item its block chose."""
    lnt, t = crush_ref.ln16(ln), m.tunables
    weight = [0x10000] * m.max_devices
    result, w = [], []
    for step in steps:
        if step[0] == "take":
            w = [step[1]]
        elif step[0] == "chooseleaf_firstn":
            numrep = step[1] if step[1] > 0 else step[1] + result_max
            block = [ITEM_NONE] * result_max
            block2 = [ITEM_NONE] * result_max
            placed = crush_ref._choose_firstn(
                m, m.buckets[w[0]], weight, x, numrep, step[2], block, 0,
                result_max, t["choose_total_tries"],
                1 if t["chooseleaf_descend_once"]
                else t["choose_total_tries"],
                t["choose_local_tries"], True, t["chooseleaf_vary_r"],
                t["chooseleaf_stable"], block2, 0, lnt)
            w = block2[:placed]
        elif step[0] == "emit":
            result += w if not truncate else w[:result_max - len(result)]
            w = []
    return result


# -- the same over an array of inputs ---------------------------------------

def map_batch(m: Map, steps, xs, result_max: int, ln: str = "exact",
              truncate: bool = True, chunk: int = 1 << 12) -> np.ndarray:
    """``do_rule`` for every x -> (N, result_max) int64, ITEM_NONE in
    unfilled places; with ``truncate`` False (N, every slot of every
    block)."""
    lnt, tables = crush_ref.ln16(ln), crush_ref._Tables(m)
    xs = np.asarray(xs, dtype=np.uint32)
    blocks, root = [], None
    for s in steps:
        if s[0] == "take":
            root = s[1]
        elif s[0] == "chooseleaf_firstn":
            numrep = s[1] if s[1] > 0 else s[1] + result_max
            blocks.append((root, min(numrep, result_max), s[2]))
    width = sum(k for _r, k, _t in blocks) if not truncate else result_max
    res = np.full((len(xs), width), ITEM_NONE, dtype=np.int64)
    for lo in range(0, len(xs), chunk):
        x = xs[lo:lo + chunk]
        # a firstn block fills its slots from the left: each block's
        # result is its items in order, ITEM_NONE after them
        rows = np.concatenate([
            crush_ref._firstn_np(m, tables, x, root, k, type_, True,
                                 m.tunables, lnt)
            for root, k, type_ in blocks], axis=1)
        order = np.argsort(rows == ITEM_NONE, axis=1, kind="stable")
        res[lo:lo + chunk] = np.take_along_axis(rows, order,
                                                axis=1)[:, :width]
    return res


def _range(start: int, n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.uint64) + np.uint64(start)).astype(np.uint32)


def sweep_counts(m: Map, steps, start: int, n: int, result_max: int,
                 ln: str = "exact", truncate: bool = True):
    """What ``crushtool --test`` reports for start .. start+n-1:
    placements per device, and the mappings short of ``result_max``."""
    rows = map_batch(m, steps, _range(start, n), result_max, ln, truncate)
    valid = rows != ITEM_NONE
    counts = np.bincount(rows[valid], minlength=m.max_devices)
    return counts.astype(np.int64), int(
        (valid.sum(axis=1) < result_max).sum())


# -- the configuration, and what a run compares with ------------------------

def build(desc: dict, classes: dict, rule_text: str, order=None):
    """(the tree as built, its shadows, the map holding both, the
    rule's steps) of a configuration; ``order`` other than the
    configuration's makes the shadows' ids in another class order."""
    base = crush_ref.build_map(desc)
    shadows = shadow_trees(base, class_of(desc, classes),
                           order or classes["order"])
    m = with_shadows(base, shadows)
    return base, shadows, m, parse_rule(rule_text, base, shadows)


def shadows_differing(names: dict, buckets: dict, shadows: dict) -> int:
    """Shadow buckets of a program's map (``names``: id -> name,
    ``buckets``: id -> object with ``items``) that are not the
    reference's: one for each shadow whose id or items differ or that
    is missing, and one for each the program has and the reference
    does not."""
    by_name = {name: bid for bid, name in names.items()}
    base_names = {bid: name for bid, name in names.items() if "~" not in name}
    bad, seen = 0, set()
    for (bid, c), want in shadows.items():
        sid = by_name.get(f"{base_names.get(bid)}~{c}")
        seen.add(sid)
        bad += sid != want.id or list(buckets[sid].items) != want.items
    return bad + sum(1 for bid, name in names.items()
                     if "~" in name and bid not in seen)


_MAPS: dict = {}


def worker_init(desc: dict, classes: dict, rule_text: str, extra: dict):
    """The maps once in each worker: ``"cfg"`` the configuration's,
    and one per entry of ``extra`` (name -> class order). A worker
    imports numpy and the reference and never touches the chip."""
    _MAPS.clear()
    for name, order in {"cfg": None, **extra}.items():
        _MAPS[name] = build(desc, classes, rule_text, order)[2:]
    crush_ref.ln16("exact")


def worker_ready() -> bool:
    return "cfg" in _MAPS


def worker_call(fn, which, *piece):
    m, steps = _MAPS[which]
    return fn(m, steps, *piece)


def _counts(m, steps, start, n, result_max, ln, truncate):
    return sweep_counts(m, steps, start, n, result_max, ln, truncate)


def _vectors(m, steps, start, n, result_max, ln, truncate):
    return map_batch(m, steps, _range(start, n), result_max, ln, truncate)


class ClassReference(crush_ref.SweepReference):
    """Counts of whole sweeps and blocks of result vectors of the
    configuration's rule on its class shadows, over a pool of CPU
    workers (``workers`` 0: in this process). ``which``: ``"cfg"``, or
    a name of ``extra`` (the shadows' ids made in that class order)."""

    def __init__(self, desc: dict, classes: dict, rule_text: str,
                 workers: int, extra: dict | None = None):
        self.desc, self.workers, self.pool = desc, workers, None
        self.base, self.shadows, self.map, self.steps = build(
            desc, classes, rule_text)
        self.klass = class_of(desc, classes)
        extra = dict(extra or {})
        if workers > 0:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor
            self.pool = ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn"),
                initializer=worker_init,
                initargs=(desc, classes, rule_text, extra))
            self._ready = [self.pool.submit(worker_ready)
                           for _ in range(workers)]
        else:
            self._maps = {name: build(desc, classes, rule_text, order)[2:]
                          for name, order in {"cfg": None, **extra}.items()}

    def _pieces(self, fn, which, start, n, *args):
        pieces = [(start + lo, min(self.PIECE, n - lo))
                  for lo in range(0, n, self.PIECE)]
        if self.pool is None:
            m, steps = self._maps[which]
            return [fn(m, steps, s, k, *args) for s, k in pieces]
        futs = [self.pool.submit(worker_call, fn, which, s, k, *args)
                for s, k in pieces]
        return [f.result() for f in futs]

    def counts(self, sweeps, result_max: int, ln: str = "exact",
               which: str = "cfg", truncate: bool = True):
        """[(start, n)] -> [(counts, bad)] in the same order."""
        out = []
        for start, n in sweeps:
            got = self._pieces(_counts, which, start, n, result_max, ln,
                               truncate)
            out.append((sum(c for c, _ in got), sum(b for _, b in got)))
        return out

    def vectors(self, start: int, n: int, result_max: int,
                ln: str = "exact", which: str = "cfg",
                truncate: bool = True) -> np.ndarray:
        """(n, result_max) result vectors of start .. start+n-1."""
        return np.concatenate(self._pieces(_vectors, which, start, n,
                                           result_max, ln, truncate))

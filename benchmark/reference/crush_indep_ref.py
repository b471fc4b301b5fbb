"""Plain reference for an erasure-coded pool's placement:
``crush_do_rule`` for a rule whose choose step is ``choose indep`` or
``chooseleaf indep``, written from src/crush/mapper.c
(``crush_choose_indep``, ``crush_do_rule``), crush.h (the step codes)
and CrushTester.cc (what a bad mapping is).

It imports nothing of the program. The map, the hash, the ``crush_ln``
table and the straw2 draw are ``crush_ref``'s; the rule comes from the
configuration file's ``rule_text`` (``parse_rule``), ``set_*`` steps
included. A position that cannot be filled stays ``CRUSH_ITEM_NONE``
where it is: for an EC pool the position IS the shard id.

Two forms of the same semantics, as in ``crush_ref``:

* ``do_rule`` -- one input at a time, mapper.c's loops as they stand.
* ``map_batch`` -- the same over an array of inputs in numpy, lane for
  lane identical (benchmark/tests hold them equal); what a run compares
  a timed sweep with, on ``crush_ref``'s pool of CPU workers.
"""

from __future__ import annotations

import numpy as np

from reference import crush_ref
from reference.crush_ref import (ITEM_NONE, Map, _descend_np, _is_out,
                                 _mix_np, _straw2_choose, _Tables, ln16)

ITEM_UNDEF = 0x7FFFFFFE
TYPES = {"osd": 0, "host": 1, "rack": 3, "root": 10}

# crush.h enum crush_opcodes, the steps this reference runs
OPCODES = {"take": 1, "choose_indep": 3, "emit": 4, "chooseleaf_indep": 7,
           "set_choose_tries": 8, "set_chooseleaf_tries": 9}


# -- the rule ---------------------------------------------------------------

def parse_rule(text: str, m: Map) -> list[tuple]:
    """The ``step`` lines of a decompiled rule -> [(name, arg, ...)].
    ``take`` names the root the way ``crushtool --build`` names it
    (``root``), or a bucket by its id."""
    steps = []
    for line in text.splitlines():
        tok = line.split("#")[0].split()
        if not tok or tok[0] != "step":
            continue
        if tok[1] == "take":
            if tok[2] == "root":
                root, = [b.id for b in m.buckets.values()
                         if b.type == TYPES["root"]]
            else:
                root = int(tok[2])
            steps.append(("take", root))
        elif tok[1] in ("choose", "chooseleaf"):
            if tok[2] != "indep" or tok[4] != "type":
                raise ValueError(f"not an indep step: {line.strip()!r}")
            steps.append((f"{tok[1]}_indep", int(tok[3]), TYPES[tok[5]]))
        elif tok[1] == "emit":
            steps.append(("emit",))
        elif tok[1] in ("set_choose_tries", "set_chooseleaf_tries"):
            steps.append((tok[1], int(tok[2])))
        else:
            raise ValueError(f"rule step {tok[1]!r} is not in the reference")
    return steps


def step_codes(steps) -> list[tuple[int, int, int]]:
    """The steps as crush.h's (op, arg1, arg2), for a look at the
    program's rule."""
    return [(OPCODES[s[0]], *(list(s[1:]) + [0, 0])[:2]) for s in steps]


# -- one input at a time: mapper.c as it stands -----------------------------

def _choose_indep(m: Map, bucket, weight, x, left, numrep, type_, out,
                  outpos, tries, recurse_tries, recurse_to_leaf, out2,
                  parent_r, ln) -> None:
    """mapper.c crush_choose_indep for straw2 buckets (the uniform
    bucket's r stride does not arise)."""
    endpos = outpos + left
    for rep in range(outpos, endpos):
        out[rep] = ITEM_UNDEF
        if out2 is not None:
            out2[rep] = ITEM_UNDEF
    ftotal = 0
    while left > 0 and ftotal < tries:
        for rep in range(outpos, endpos):
            if out[rep] != ITEM_UNDEF:
                continue
            in_ = bucket
            while True:
                r = rep + parent_r + numrep * ftotal
                if not in_.items:
                    break                       # empty bucket: next round
                item = _straw2_choose(in_, x, r, ln)
                if item >= m.max_devices:
                    out[rep] = ITEM_NONE        # bad item: given up
                    if out2 is not None:
                        out2[rep] = ITEM_NONE
                    left -= 1
                    break
                itemtype = m.item_type(item)
                if itemtype != type_:
                    if item >= 0 or item not in m.buckets:
                        out[rep] = ITEM_NONE    # bad item type
                        if out2 is not None:
                            out2[rep] = ITEM_NONE
                        left -= 1
                        break
                    in_ = m.buckets[item]
                    continue
                if any(out[i] == item for i in range(outpos, endpos)):
                    break                       # collision
                if recurse_to_leaf:
                    if item < 0:
                        _choose_indep(m, m.buckets[item], weight, x, 1,
                                      numrep, 0, out2, rep, recurse_tries,
                                      0, False, None, r, ln)
                        if out2[rep] == ITEM_NONE:
                            break               # placed nothing; no leaf
                    else:
                        out2[rep] = item        # we already have a leaf
                if itemtype == 0 and _is_out(weight, item, x):
                    break
                out[rep] = item
                left -= 1
                break
        ftotal += 1
    for rep in range(outpos, endpos):
        if out[rep] == ITEM_UNDEF:
            out[rep] = ITEM_NONE
        if out2 is not None and out2[rep] == ITEM_UNDEF:
            out2[rep] = ITEM_NONE


def do_rule(m: Map, steps, x: int, result_max: int, weight=None,
            ln: str = "exact") -> list[int]:
    """mapper.c crush_do_rule for set_* / take / choose[leaf] indep /
    emit. The result keeps its holes."""
    lnt = ln16(ln)
    if weight is None:
        weight = [0x10000] * m.max_devices
    choose_tries = m.tunables["choose_total_tries"]
    choose_leaf_tries = 0
    result, w = [], []
    for step in steps:
        if step[0] == "take":
            w = [step[1]]
        elif step[0] == "set_choose_tries":
            if step[1] > 0:
                choose_tries = step[1]
        elif step[0] == "set_chooseleaf_tries":
            if step[1] > 0:
                choose_leaf_tries = step[1]
        elif step[0] in ("choose_indep", "chooseleaf_indep"):
            leaf = step[0] == "chooseleaf_indep"
            o, c = [], []
            for wi in w:
                numrep = step[1] if step[1] > 0 else step[1] + result_max
                if wi >= 0:
                    if step[2] == 0:
                        o.append(wi)
                        c.append(wi)
                    continue
                out_size = min(numrep, result_max - len(o))
                block = [ITEM_NONE] * out_size
                block2 = [ITEM_NONE] * out_size
                _choose_indep(m, m.buckets[wi], weight, x, out_size, numrep,
                              step[2], block, 0, choose_tries,
                              choose_leaf_tries or 1, leaf, block2, 0, lnt)
                o.extend(block)
                c.extend(block2)
            w = c if leaf else o
        elif step[0] == "emit":
            result.extend(w)
            w = []
        else:
            raise ValueError(f"rule step {step[0]!r} is not in the reference")
    return result


def is_bad(result, num_rep: int) -> bool:
    """CrushTester::test: a mapping is bad when it has another size
    than num_rep or holds a CRUSH_ITEM_NONE."""
    return len(result) != num_rep or ITEM_NONE in result


# -- the same rule over an array of inputs ----------------------------------

def _hash32_2_np(a, b):
    """crush_hash32_rjenkins1_2 over uint32 arrays that broadcast."""
    a, b = np.broadcast_arrays(a, b)
    h = np.uint32(crush_ref.HASH_SEED) ^ a ^ b
    x = np.full(h.shape, 231232, dtype=np.uint32)
    y = np.full(h.shape, 1232, dtype=np.uint32)
    a, b, h = _mix_np(a, b, h)
    x, a, h = _mix_np(x, a, h)
    b, y, h = _mix_np(b, y, h)
    return h


def _is_out_np(weight, item, x):
    """is_out for each lane's device ``item`` (all >= 0)."""
    if weight is None:
        return np.zeros(len(item), dtype=bool)
    weight = np.asarray(weight, dtype=np.int64)
    known = item < len(weight)
    w = weight[np.where(known, item, 0)]
    with np.errstate(over="ignore"):
        h = _hash32_2_np(x, item.astype(np.uint32)) & np.uint32(0xFFFF)
    out = np.where(w >= 0x10000, False,
                   np.where(w == 0, True, h.astype(np.int64) >= w))
    return out | ~known


def _indep_np(m, tables, x, root, out_size, numrep, type_, leaf, tries,
              recurse_tries, weight, ln):
    n = len(x)
    out = np.full((n, out_size), ITEM_UNDEF, dtype=np.int64)    # domain
    out2 = np.full((n, out_size), ITEM_UNDEF, dtype=np.int64)   # leaves
    for ftotal in range(tries):
        if not (out == ITEM_UNDEF).any():
            break
        for rep in range(out_size):
            live = np.nonzero(out[:, rep] == ITEM_UNDEF)[0]
            if not len(live):
                continue
            r = np.full(len(live), rep + numrep * ftotal, dtype=np.int64)
            xl = x[live]
            item = _descend_np(m, tables, np.full(len(live), root), xl, r,
                               type_, ln)
            ok = ~(out[live] == item[:, None]).any(axis=1)
            leaf_item = item.copy()
            if leaf and type_ != 0:
                # the recursion: left 1, parent_r = r, recurse_tries
                # rounds of one descent to a device
                todo = np.nonzero(ok)[0]
                ok[:] = False
                for ft2 in range(recurse_tries):
                    if not len(todo):
                        break
                    r2 = rep + r[todo] + numrep * ft2
                    dev = _descend_np(m, tables, item[todo], xl[todo], r2,
                                      0, ln)
                    good = ~_is_out_np(weight, dev, xl[todo])
                    leaf_item[todo[good]] = dev[good]
                    ok[todo[good]] = True
                    todo = todo[~good]
            elif type_ == 0:
                ok &= ~_is_out_np(weight, item, xl)
            out[live[ok], rep] = item[ok]
            out2[live[ok], rep] = leaf_item[ok]
    res = out2 if leaf else out
    res[out == ITEM_UNDEF] = ITEM_NONE
    return res


def map_batch(m: Map, steps, xs, result_max: int, weight=None,
              ln: str = "exact", chunk: int = 1 << 12) -> np.ndarray:
    """``do_rule`` for every x of ``xs`` -> (N, result_max) int64, holes
    as ITEM_NONE. The rule is [set_*] / take / choose[leaf] indep /
    emit on a tree of straw2 buckets that holds every item it names."""
    choose_tries = m.tunables["choose_total_tries"]
    leaf_tries, root, choose = 0, None, None
    for s in steps:
        if s[0] == "set_choose_tries" and s[1] > 0:
            choose_tries = s[1]
        elif s[0] == "set_chooseleaf_tries" and s[1] > 0:
            leaf_tries = s[1]
        elif s[0] == "take":
            root = s[1]
        elif s[0] in ("choose_indep", "chooseleaf_indep"):
            if choose is not None:
                raise ValueError("map_batch covers one choose step")
            choose = s
    op, numrep, type_ = choose
    numrep = numrep if numrep > 0 else numrep + result_max
    out_size = min(numrep, result_max)
    lnt, tables = ln16(ln), _Tables(m)
    xs = np.asarray(xs, dtype=np.uint32)
    res = np.full((len(xs), result_max), ITEM_NONE, dtype=np.int64)
    for lo in range(0, len(xs), chunk):
        res[lo:lo + chunk, :out_size] = _indep_np(
            m, tables, xs[lo:lo + chunk], root, out_size, numrep, type_,
            op == "chooseleaf_indep", choose_tries, leaf_tries or 1,
            weight, lnt)
    return res


def _range(start: int, n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.uint64) + np.uint64(start)).astype(np.uint32)


def sweep_counts(m: Map, steps, start: int, n: int, result_max: int,
                 weight=None, ln: str = "exact") -> tuple[np.ndarray, int]:
    """What ``crushtool --test`` reports for inputs start .. start+n-1:
    placements per device, and the bad mappings as upstream counts
    them (``is_bad``)."""
    rows = map_batch(m, steps, _range(start, n), result_max, weight, ln)
    valid = rows != ITEM_NONE
    counts = np.bincount(rows[valid], minlength=m.max_devices)
    return counts.astype(np.int64), int((~valid).any(axis=1).sum())


# -- on crush_ref's worker processes ----------------------------------------

def worker_counts(steps, start, n, result_max, ln):
    return sweep_counts(crush_ref._WORKER["map"], steps, start, n,
                        result_max, None, ln)


def worker_vectors(steps, start, n, result_max, ln):
    return map_batch(crush_ref._WORKER["map"], steps, _range(start, n),
                     result_max, None, ln)


class IndepReference(crush_ref.SweepReference):
    """``crush_ref.SweepReference``'s pool (each worker builds the map
    from the description) mapping the configuration's indep rule."""

    def __init__(self, desc: dict, rule_text: str, workers: int):
        super().__init__(desc, workers)
        self.map = crush_ref.build_map(desc)
        self.steps = parse_rule(rule_text, self.map)

    def _pieces(self, fn, start, n, result_max, ln):
        pieces = [(start + lo, min(self.PIECE, n - lo))
                  for lo in range(0, n, self.PIECE)]
        if self.pool is None:
            return [fn(self.steps, s, k, result_max, ln) for s, k in pieces]
        futs = [self.pool.submit(fn, self.steps, s, k, result_max, ln)
                for s, k in pieces]
        return [f.result() for f in futs]

    def counts(self, sweeps, result_max: int, ln: str = "exact"):
        """[(start, n)] -> [(counts, bad)] in the same order."""
        out = []
        for start, n in sweeps:
            got = self._pieces(worker_counts, start, n, result_max, ln)
            out.append((sum(c for c, _ in got), sum(b for _, b in got)))
        return out

    def vectors(self, start: int, n: int, result_max: int,
                ln: str = "exact") -> np.ndarray:
        """(n, result_max) result vectors of start .. start+n-1."""
        return np.concatenate(
            self._pieces(worker_vectors, start, n, result_max, ln))

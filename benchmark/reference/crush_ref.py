"""Plain reference for CRUSH placement: ``crush_do_rule`` for straw2
trees and ``choose``/``chooseleaf firstn`` rules, written from
src/crush/mapper.c, hash.c and crush_ln_table.h.

It imports nothing of the program and takes nothing the program made:
the map is built here from the configuration file's description
(``build_map``), the ln table from upstream's generating formulas.

Two forms of the same semantics:

* ``do_rule`` -- one input at a time, the loops of mapper.c as they
  stand there. Slow (tens of inputs a second); the spec.
* ``map_batch`` -- the same rule over an array of inputs in numpy, lane
  by lane identical to ``do_rule`` (benchmark/tests/test_references.py
  holds them equal). This is what a run compares a timed sweep with: a
  sweep returns only per-device counts, so checking one means mapping
  all of its inputs.

``ln="float32"`` is the control: straw2's fixed-point ``crush_ln``
replaced by a float32 log2, the cheaper arithmetic a faster kernel
would be tempted by. It moves a few placements in a million.
"""

from __future__ import annotations

import numpy as np

ITEM_NONE = 0x7FFFFFFF
S64_MIN = -(1 << 63)
HASH_SEED = 1315423911
U32 = 0xFFFFFFFF


# -- the map ----------------------------------------------------------------

class Bucket:
    __slots__ = ("id", "type", "items", "weights")

    def __init__(self, id_, type_, items, weights):
        self.id, self.type = id_, type_
        self.items, self.weights = list(items), list(weights)


class Map:
    """buckets by (negative) id, type of every bucket, the rule's steps
    and the tunables. Devices are 0..max_devices-1, type 0."""

    def __init__(self, buckets, max_devices, rule, tunables):
        self.buckets = {b.id: b for b in buckets}
        self.max_devices = max_devices
        self.rule = rule
        self.tunables = tunables

    def item_type(self, item: int) -> int:
        return 0 if item >= 0 else self.buckets[item].type


JEWEL = {"choose_local_tries": 0, "choose_local_fallback_tries": 0,
         "choose_total_tries": 50, "chooseleaf_descend_once": 1,
         "chooseleaf_vary_r": 1, "chooseleaf_stable": 1}


def build_map(desc: dict) -> Map:
    """The tree ``crushtool --build`` makes for the description: hosts
    of consecutive OSDs get ids -1, -2, ... in order, then the racks,
    then the root; a bucket's weight is the sum of its items'. The rule
    is ``take root; chooseleaf firstn 0 type <failure_domain>; emit``
    (``choose`` when the domain is the OSD)."""
    n, hosts, racks = desc["osds"], desc["hosts"], desc.get("racks", 0)
    w1 = desc.get("osd_weight", 0x10000)
    types = {"osd": 0, "host": 1, "rack": 3, "root": 10}
    next_id = -1
    buckets, level = [], []
    if hosts:
        per = n // hosts
        if per * hosts != n:
            raise ValueError("osds must divide evenly into hosts")
        for h in range(hosts):
            b = Bucket(next_id, types["host"],
                       range(h * per, (h + 1) * per), [w1] * per)
            next_id -= 1
            buckets.append(b)
            level.append(b)
    else:
        level = None
    if hosts and racks:
        per = max(1, hosts // racks)
        rk = []
        for r in range(racks):
            hs = level[r * per:(r + 1) * per] if r < racks - 1 \
                else level[(racks - 1) * per:]
            b = Bucket(next_id, types["rack"], [h.id for h in hs],
                       [sum(h.weights) for h in hs])
            next_id -= 1
            buckets.append(b)
            rk.append(b)
        level = rk
    if level is None:
        root = Bucket(next_id, types["root"], range(n), [w1] * n)
    else:
        root = Bucket(next_id, types["root"], [b.id for b in level],
                      [sum(b.weights) for b in level])
    buckets.append(root)
    fd = types[desc["failure_domain"]]
    op = "choose_firstn" if fd == 0 else "chooseleaf_firstn"
    rule = [("take", root.id), (op, 0, fd), ("emit",)]
    return Map(buckets, n, rule, dict(JEWEL, **desc.get("tunables", {})))


# -- rjenkins1 and crush_ln -------------------------------------------------

def _mix(a, b, c):
    a = (a - b - c) & U32; a ^= c >> 13
    b = (b - c - a) & U32; b ^= (a << 8) & U32
    c = (c - a - b) & U32; c ^= b >> 13
    a = (a - b - c) & U32; a ^= c >> 12
    b = (b - c - a) & U32; b ^= (a << 16) & U32
    c = (c - a - b) & U32; c ^= b >> 5
    a = (a - b - c) & U32; a ^= c >> 3
    b = (b - c - a) & U32; b ^= (a << 10) & U32
    c = (c - a - b) & U32; c ^= b >> 15
    return a, b, c


def hash32_2(a: int, b: int) -> int:
    a &= U32; b &= U32
    h = HASH_SEED ^ a ^ b
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash32_3(a: int, b: int, c: int) -> int:
    a &= U32; b &= U32; c &= U32
    h = HASH_SEED ^ a ^ b ^ c
    x, y = 231232, 1232
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def _ln_tables():
    """crush_ln_table.h by its generating formulas: RH = ceil(2^56 /
    index1), LH = round(2^48 log2(index1 / 256)) for index1 = 256, 258,
    ..., 512, and LL[k] = round(2^48 log2(1 + k / 2^15))."""
    i1 = np.arange(256, 514, 2)
    rh = [-((-(1 << 56)) // int(i)) for i in i1]
    lh = [int(v) for v in np.rint(2.0 ** 48 * np.log2(i1 / 256.0))]
    ll = [int(v) for v in np.rint(
        2.0 ** 48 * np.log2(1.0 + np.arange(256) / 2.0 ** 15))]
    return rh, lh, ll


def _crush_ln(xin: int, rh, lh, ll) -> int:
    """mapper.c crush_ln: 2^44 log2(xin + 1), table driven."""
    x = xin + 1
    iexpon = 15
    if not x & 0x18000:
        bits = 16 - x.bit_length()
        x <<= bits
        iexpon = 15 - bits
    j = ((x >> 8) << 1) - 256 >> 1
    xl64 = (x * rh[j]) >> 48
    result = iexpon << 44
    return result + ((lh[j] + ll[xl64 & 0xFF]) >> 4)


_LN16 = {}


def ln16(kind: str = "exact") -> np.ndarray:
    """crush_ln(u) - 2^48 for every 16-bit u (all <= 0), int64.
    ``float32`` is the control's table: the same quantity through a
    float32 log2."""
    if kind not in _LN16:
        if kind == "exact":
            rh, lh, ll = _ln_tables()
            t = np.array([_crush_ln(u, rh, lh, ll) - (1 << 48)
                          for u in range(65536)], dtype=np.int64)
        elif kind == "float32":
            u = np.arange(65536, dtype=np.float32) + np.float32(1)
            t = ((np.log2(u) - np.float32(16)) * np.float32(2.0 ** 44)
                 ).astype(np.float32).astype(np.int64)
        else:
            raise ValueError(f"unknown ln table {kind!r}")
        t.flags.writeable = False
        _LN16[kind] = t
    return _LN16[kind]


# -- one input at a time: mapper.c as it stands -----------------------------

def _straw2_choose(b: Bucket, x: int, r: int, ln) -> int:
    high, high_draw = 0, 0
    for i, (item, w) in enumerate(zip(b.items, b.weights)):
        if w:
            l = int(ln[hash32_3(x, item, r) & 0xFFFF])
            draw = -((-l) // w)             # C division truncates
        else:
            draw = S64_MIN
        if i == 0 or draw > high_draw:
            high, high_draw = i, draw
    return b.items[high]


def _is_out(weight, item: int, x: int) -> bool:
    if item >= len(weight):
        return True
    w = weight[item]
    if w >= 0x10000:
        return False
    if w == 0:
        return True
    return (hash32_2(x, item) & 0xFFFF) >= w


def _choose_firstn(m: Map, bucket, weight, x, numrep, type_, out, outpos,
                   out_size, tries, recurse_tries, local_retries,
                   recurse_to_leaf, vary_r, stable, out2, parent_r, ln):
    """mapper.c crush_choose_firstn without the legacy local-fallback
    branch (choose_local_fallback_tries is 0 since bobtail)."""
    count = out_size
    rep = 0 if stable else outpos
    while rep < numrep and count > 0:
        ftotal, skip_rep, item = 0, False, None
        retry_descent = True
        while retry_descent:
            retry_descent = False
            in_, flocal = bucket, 0
            retry_bucket = True
            while retry_bucket:
                retry_bucket = False
                r = rep + parent_r + ftotal
                if not in_.items:
                    reject, collide = True, False
                else:
                    item = _straw2_choose(in_, x, r, ln)
                    if item >= m.max_devices:
                        skip_rep = True
                        break
                    itemtype = m.item_type(item)
                    if itemtype != type_:
                        if item >= 0 or item not in m.buckets:
                            skip_rep = True
                            break
                        in_ = m.buckets[item]
                        retry_bucket = True
                        continue
                    collide = any(out[i] == item for i in range(outpos))
                    reject = False
                    if not collide and recurse_to_leaf:
                        if item < 0:
                            sub_r = r >> (vary_r - 1) if vary_r else 0
                            placed = _choose_firstn(
                                m, m.buckets[item], weight, x,
                                1 if stable else outpos + 1, 0, out2,
                                outpos, count, recurse_tries, 0,
                                local_retries, False, vary_r, stable,
                                None, sub_r, ln)
                            if placed <= outpos:
                                reject = True
                        else:
                            out2[outpos] = item
                    if not reject and not collide and itemtype == 0:
                        reject = _is_out(weight, item, x)
                if reject or collide:
                    ftotal += 1
                    flocal += 1
                    if collide and flocal <= local_retries:
                        retry_bucket = True
                    elif ftotal < tries:
                        retry_descent = True
                    else:
                        skip_rep = True
        if not skip_rep:
            out[outpos] = item
            outpos += 1
            count -= 1
        rep += 1
    return outpos


def do_rule(m: Map, x: int, result_max: int, weight=None,
            ln: str = "exact") -> list[int]:
    """mapper.c crush_do_rule for take / choose[leaf] firstn / emit."""
    lnt = ln16(ln)
    if weight is None:
        weight = [0x10000] * m.max_devices
    t = m.tunables
    result, w = [], []
    for step in m.rule:
        if step[0] == "take":
            w = [step[1]]
        elif step[0] in ("choose_firstn", "chooseleaf_firstn"):
            leaf = step[0] == "chooseleaf_firstn"
            o, c = [], []
            for wi in w:
                numrep = step[1] if step[1] > 0 else step[1] + result_max
                if wi >= 0:
                    if step[2] == 0:
                        o.append(wi)
                        c.append(wi)
                    continue
                recurse_tries = 1 if t["chooseleaf_descend_once"] \
                    else t["choose_total_tries"]
                block = [ITEM_NONE] * result_max
                block2 = [ITEM_NONE] * result_max
                placed = _choose_firstn(
                    m, m.buckets[wi], weight, x, numrep, step[2], block,
                    0, result_max - len(o), t["choose_total_tries"],
                    recurse_tries, t["choose_local_tries"], leaf,
                    t["chooseleaf_vary_r"], t["chooseleaf_stable"],
                    block2, 0, lnt)
                o.extend(block[:placed])
                c.extend(block2[:placed])
            w = c if leaf else o
        elif step[0] == "emit":
            result.extend(w)
            w = []
        else:
            raise ValueError(f"rule step {step[0]!r} is not in the reference")
    return result


# -- the same rule over an array of inputs ----------------------------------

def _mix_np(a, b, c):
    a = a - b; a -= c; a ^= c >> np.uint32(13)
    b = b - c; b -= a; b ^= a << np.uint32(8)
    c = c - a; c -= b; c ^= b >> np.uint32(13)
    a -= b; a -= c; a ^= c >> np.uint32(12)
    b -= c; b -= a; b ^= a << np.uint32(16)
    c -= a; c -= b; c ^= b >> np.uint32(5)
    a -= b; a -= c; a ^= c >> np.uint32(3)
    b -= c; b -= a; b ^= a << np.uint32(10)
    c -= a; c -= b; c ^= b >> np.uint32(15)
    return a, b, c


def _hash32_3_np(a, b, c):
    """crush_hash32_rjenkins1_3 over uint32 arrays that broadcast."""
    a, b, c = np.broadcast_arrays(a, b, c)
    h = np.uint32(HASH_SEED) ^ a ^ b ^ c
    x = np.full(h.shape, 231232, dtype=np.uint32)
    y = np.full(h.shape, 1232, dtype=np.uint32)
    a, b, h = _mix_np(a, b, h)
    c, x, h = _mix_np(c, x, h)
    y, a, h = _mix_np(y, a, h)
    b, x, h = _mix_np(b, x, h)
    y, c, h = _mix_np(y, c, h)
    return h


class _Tables:
    """Buckets of one type as padded arrays, so that lanes standing in
    different buckets of that type draw together."""

    def __init__(self, m: Map):
        self.by_type = {}
        self.type_of = {}
        self.row_of = {}
        for type_ in sorted({b.type for b in m.buckets.values()}):
            bs = [b for b in m.buckets.values() if b.type == type_]
            width = max(len(b.items) for b in bs)
            items = np.zeros((len(bs), width), dtype=np.int64)
            weights = np.zeros((len(bs), width), dtype=np.int64)
            for row, b in enumerate(bs):
                items[row, :len(b.items)] = b.items
                weights[row, :len(b.items)] = b.weights
                self.type_of[b.id] = type_
                self.row_of[b.id] = row
            self.by_type[type_] = (items, weights)
        low = min(m.buckets)
        self.types = np.zeros(-low + 1, dtype=np.int64)
        self.rows = np.zeros(-low + 1, dtype=np.int64)
        for bid in m.buckets:
            self.types[-bid] = self.type_of[bid]
            self.rows[-bid] = self.row_of[bid]


def _straw2_np(tables: _Tables, cur, x, r, ln):
    """bucket_straw2_choose for each lane: lane i stands in bucket
    ``cur[i]`` and draws with (x[i], r[i])."""
    out = np.empty(len(cur), dtype=np.int64)
    types = tables.types[-cur]
    for type_ in np.unique(types):
        lanes = np.nonzero(types == type_)[0]
        items, weights = tables.by_type[int(type_)]
        rows = tables.rows[-cur[lanes]]
        it, w = items[rows], weights[rows]
        with np.errstate(over="ignore"):
            h = _hash32_3_np(x[lanes, None], it.astype(np.uint32),
                             r[lanes, None].astype(np.uint32))
        l = ln[h & np.uint32(0xFFFF)]
        draw = np.where(w > 0, -((-l) // np.maximum(w, 1)), S64_MIN)
        # the first of equal draws wins, as `draw > high_draw` does
        out[lanes] = it[np.arange(len(lanes)), np.argmax(draw, axis=1)]
    return out


def _descend_np(m, tables, start, x, r, type_, ln):
    """Follow straw2 choices from ``start`` down to an item of
    ``type_``. Lanes descend while what they hold is a bucket of
    another type."""
    item = start.copy()
    todo = np.arange(len(item))
    while len(todo):
        item[todo] = _straw2_np(tables, item[todo], x[todo], r[todo], ln)
        held = item[todo]
        is_bucket = held < 0
        t = np.zeros(len(todo), dtype=np.int64)
        t[is_bucket] = tables.types[-held[is_bucket]]
        if (~is_bucket & (type_ != 0)).any():
            raise ValueError("a device above the wanted type")
        todo = todo[t != type_]
    return item


def map_batch(m: Map, xs, result_max: int, ln: str = "exact",
              chunk: int = 1 << 12) -> np.ndarray:
    """``do_rule`` for every x of ``xs`` -> (N, result_max) int64 with
    ITEM_NONE in unfilled places. Every device weight is in (no
    ``is_out`` rejection), the rule is take / choose[leaf] firstn 0 /
    emit, and the tunables are bobtail's or later (no local retries,
    ``descend_once``, ``vary_r`` 0 or 1, ``stable`` 1)."""
    t = m.tunables
    if (t["choose_local_tries"] or t["choose_local_fallback_tries"]
            or not t["chooseleaf_descend_once"]
            or not t["chooseleaf_stable"] or t["chooseleaf_vary_r"] > 1):
        raise ValueError("map_batch covers jewel-style tunables only")
    (_take, root), (op, numrep, type_), _emit = m.rule
    numrep = numrep if numrep > 0 else numrep + result_max
    leaf = op == "chooseleaf_firstn"
    lnt, tables = ln16(ln), _Tables(m)
    xs = np.asarray(xs, dtype=np.uint32)
    res = np.full((len(xs), result_max), ITEM_NONE, dtype=np.int64)
    for lo in range(0, len(xs), chunk):
        res[lo:lo + chunk] = _firstn_np(
            m, tables, xs[lo:lo + chunk], root, min(numrep, result_max),
            type_, leaf, t, lnt)
    return res


def _firstn_np(m, tables, x, root, numrep, type_, leaf, t, ln):
    n = len(x)
    out = np.full((n, numrep), ITEM_NONE, dtype=np.int64)    # domain
    out2 = np.full((n, numrep), ITEM_NONE, dtype=np.int64)   # leaves
    outpos = np.zeros(n, dtype=np.int64)
    tries = t["choose_total_tries"]
    for rep in range(numrep):
        ftotal = np.zeros(n, dtype=np.int64)
        live = np.arange(n)
        while len(live):
            r = rep + ftotal[live]
            item = _descend_np(m, tables, np.full(len(live), root), x[live],
                               r, type_, ln)
            pos = outpos[live]
            placed = np.arange(numrep)[None, :] < pos[:, None]
            bad = ((out[live] == item[:, None]) & placed).any(axis=1)
            leaf_item = item
            if leaf and type_ != 0:
                # the recursion: one descent to a device, one try
                # (descend_once), r' = r >> (vary_r - 1) or 0
                sub_r = r if t["chooseleaf_vary_r"] else np.zeros_like(r)
                ok = np.nonzero(~bad)[0]
                leaf_item = item.copy()
                leaf_item[ok] = _descend_np(
                    m, tables, item[ok], x[live][ok], sub_r[ok], 0, ln)
                bad |= ((out2[live] == leaf_item[:, None])
                        & placed).any(axis=1)
            good = live[~bad]
            out[good, outpos[good]] = item[~bad]
            out2[good, outpos[good]] = leaf_item[~bad]
            outpos[good] += 1
            ftotal[live[bad]] += 1
            live = live[bad]
            live = live[ftotal[live] < tries]
    return out2 if leaf else out


def sweep_counts(m: Map, start: int, n: int, result_max: int,
                 ln: str = "exact") -> tuple[np.ndarray, int]:
    """What ``crushtool --test`` reports for inputs start .. start+n-1:
    placements per device, and how many inputs got fewer than
    ``result_max`` devices."""
    xs = (np.arange(n, dtype=np.uint64) + np.uint64(start)).astype(np.uint32)
    rows = map_batch(m, xs, result_max, ln)
    valid = rows != ITEM_NONE
    counts = np.bincount(rows[valid], minlength=m.max_devices)
    return counts.astype(np.int64), int((valid.sum(axis=1) < result_max).sum())


# -- a sweep's counts on worker processes -----------------------------------

_WORKER = {}


def worker_init(desc: dict) -> None:
    """Build the map and the ln tables once in each worker. A worker
    imports numpy and this module and nothing else: it never touches
    the chip."""
    _WORKER["map"] = build_map(desc)
    ln16("exact")


def worker_ready() -> bool:
    return "map" in _WORKER


def worker_counts(start: int, n: int, result_max: int, ln: str):
    return sweep_counts(_WORKER["map"], start, n, result_max, ln)


class SweepReference:
    """Counts of whole sweeps, cut into pieces over a pool of CPU
    workers. Start it early (the workers build their tables while the
    device compiles); ``counts`` blocks until the pieces are in."""

    PIECE = 1 << 16

    def __init__(self, desc: dict, workers: int):
        self.desc, self.workers, self.pool = desc, workers, None
        if workers > 0:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor
            self.pool = ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn"),
                initializer=worker_init, initargs=(desc,))
            self._ready = [self.pool.submit(worker_ready)
                           for _ in range(workers)]
        else:
            worker_init(desc)

    def counts(self, sweeps, result_max: int, ln: str = "exact"):
        """[(start, n)] -> [(counts, bad)] in the same order."""
        pieces = [(i, s + lo, min(self.PIECE, n - lo))
                  for i, (s, n) in enumerate(sweeps)
                  for lo in range(0, n, self.PIECE)]
        if self.pool is None:
            got = [worker_counts(s, n, result_max, ln)
                   for _i, s, n in pieces]
        else:
            futs = [self.pool.submit(worker_counts, s, n, result_max, ln)
                    for _i, s, n in pieces]
            got = [f.result() for f in futs]
        out = [[0, 0] for _ in sweeps]
        for (i, _s, _n), (c, bad) in zip(pieces, got):
            out[i][0] = out[i][0] + c
            out[i][1] += bad
        return [(c, b) for c, b in out]

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)
            self.pool = None

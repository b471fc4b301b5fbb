"""Plain reference for placement on a map that carries a compat
weight-set (``choose_args`` id -1, one position, no id overrides):
``crush_do_rule`` with ``get_choose_arg_weights`` substituting every
bucket's draw weights, written from src/crush/mapper.c
(``bucket_straw2_choose``, ``get_choose_arg_weights``) and
CrushWrapper.cc (``choose_args_adjust_item_weight``: what a weight-set
looks like after every OSD's weight was set).

It imports nothing of the program. The tree is ``crush_ref.build_map``'s;
the weight-set is derived here from the configuration file's
description (``weight_set``): the generator gives each OSD its weight, a
host's vector is its OSDs' weights, and a bucket's entry in its
parent's vector is the sum of the bucket's own vector. With one
position and no ids the substitution is the whole of it: a straw2 draw
divides by the vector's entry where it divided by the CRUSH weight. So
the mapping is ``crush_ref``'s ``do_rule`` / ``map_batch`` (hash,
``crush_ln``, draw) on the tree with every bucket's weights replaced
(``substituted``). ``is_out`` reads the reweight vector, which a
weight-set does not touch.

``WeightSetReference`` is ``crush_ref.SweepReference`` with a worker
pool of its own: each worker builds the substituted map from the
description, and beside it any ``extra`` maps a control asks for (the
tree with other vectors; ``"none"`` is the tree as built, the
weight-set ignored).
"""

from __future__ import annotations

import numpy as np

from reference import crush_ref
from reference.crush_ref import Bucket, Map

COMPAT = -1                  # CrushWrapper::DEFAULT_CHOOSE_ARGS


def osd_weights(desc: dict, ws: dict) -> list[int]:
    """The configuration's generator: OSD ``o`` weighs ``int(65536 *
    f[o])``, ``f = numpy.random.default_rng(rng_seed).uniform(low,
    high, osds)``."""
    f = np.random.default_rng(int(ws["rng_seed"])).uniform(
        float(ws["low"]), float(ws["high"]), int(desc["osds"]))
    return [int(65536 * v) for v in f]


def weight_set(m: Map, osd_w) -> dict[int, list[int]]:
    """bucket id -> its vector: a device's entry is its weight, a
    bucket's entry the sum of that bucket's vector. ``build_map`` lists
    children before parents."""
    vectors: dict[int, list[int]] = {}
    for b in m.buckets.values():
        vectors[b.id] = [int(osd_w[i]) if i >= 0 else sum(vectors[i])
                         for i in b.items]
    return vectors


def substituted(m: Map, vectors: dict) -> Map:
    """The tree with each bucket's draw weights replaced by its vector
    (a bucket the set has no vector for keeps its CRUSH weights, as
    ``get_choose_arg_weights`` falls back)."""
    return Map([Bucket(b.id, b.type, b.items, vectors.get(b.id, b.weights))
                for b in m.buckets.values()],
               m.max_devices, m.rule, m.tunables)


def build(desc: dict, ws: dict) -> tuple[Map, dict, Map]:
    """(the tree as built, the weight-set's vectors, the tree with
    them substituted) for a configuration's ``map`` and
    ``weight_set``."""
    if int(ws.get("positions", 1)) != 1:
        raise ValueError("the reference covers one-position weight-sets")
    base = crush_ref.build_map(desc)
    vectors = weight_set(base, osd_weights(desc, ws))
    return base, vectors, substituted(base, vectors)


def do_rule(m: Map, x: int, result_max: int, ln: str = "exact") -> list[int]:
    """One input at a time on a ``substituted`` map: mapper.c's loops."""
    return crush_ref.do_rule(m, x, result_max, None, ln)


def map_batch(m: Map, xs, result_max: int, ln: str = "exact") -> np.ndarray:
    """The same over an array of inputs, lane for lane."""
    return crush_ref.map_batch(m, xs, result_max, ln)


def vectors_differing(program_args: dict | None, vectors: dict) -> int:
    """Entries of a program's weight-set (``{bucket id: object with
    weight_set [[...]] and ids}``) that are not the reference's: a
    wrong weight counts one, a vector of another length, a missing or
    surplus bucket, more than one position or an id override each
    count the vector's length."""
    if program_args is None:
        return sum(len(v) for v in vectors.values())
    bad = 0
    for bid, want in vectors.items():
        arg = program_args.get(bid)
        sets = getattr(arg, "weight_set", None)
        if not sets or len(sets) != 1 or len(sets[0]) != len(want) \
                or getattr(arg, "ids", None):
            bad += len(want)
            continue
        bad += sum(int(g) != w for g, w in zip(sets[0], want))
    for bid, arg in program_args.items():
        if bid not in vectors:
            bad += max(1, sum(len(v) for v in arg.weight_set))
    return bad


# -- sweeps and vectors on worker processes ---------------------------------

_MAPS: dict[str, Map] = {}


def _range(start: int, n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.uint64) + np.uint64(start)).astype(np.uint32)


def build_maps(desc: dict, ws: dict, extra: dict) -> dict[str, Map]:
    """``"ws"`` the configuration's map, ``"none"`` the tree as built,
    and one per entry of ``extra`` (name -> vectors)."""
    base, _vectors, m = build(desc, ws)
    maps = {"ws": m, "none": base}
    for name, vectors in extra.items():
        maps[name] = substituted(base, vectors)
    return maps


def worker_init(desc: dict, ws: dict, extra: dict) -> None:
    """Build the maps and the ln table once in each worker. A worker
    imports numpy and the reference and nothing else: it never touches
    the chip."""
    _MAPS.clear()
    _MAPS.update(build_maps(desc, ws, extra))
    crush_ref.ln16("exact")


def worker_ready() -> bool:
    return "ws" in _MAPS


def _counts(m, start, n, result_max, ln):
    return crush_ref.sweep_counts(m, start, n, result_max, ln)


def _vectors(m, start, n, result_max, ln):
    return crush_ref.map_batch(m, _range(start, n), result_max, ln)


def worker_call(fn, which, *piece):
    """``fn`` (``_counts`` or ``_vectors``) on this worker's map
    ``which``."""
    return fn(_MAPS[which], *piece)


class WeightSetReference(crush_ref.SweepReference):
    """Counts of whole sweeps and blocks of result vectors on the map
    with the configuration's weight-set, over a pool of CPU workers
    (``workers`` 0: in this process). ``which`` picks the map:
    ``"ws"``, ``"none"`` or a name of ``extra``."""

    def __init__(self, desc: dict, ws: dict, workers: int,
                 extra: dict | None = None):
        self.desc, self.ws, self.workers, self.pool = desc, ws, workers, None
        self.base, self.weight_set, self.map = build(desc, ws)
        self.osd_weights = osd_weights(desc, ws)
        self.extra = extra = dict(extra or {})
        if workers > 0:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor
            self.pool = ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("spawn"),
                initializer=worker_init, initargs=(desc, ws, extra))
            self._ready = [self.pool.submit(worker_ready)
                           for _ in range(workers)]
        else:
            self._maps = build_maps(desc, ws, extra)

    def _pieces(self, fn, which, start, n, result_max, ln):
        pieces = [(start + lo, min(self.PIECE, n - lo))
                  for lo in range(0, n, self.PIECE)]
        if self.pool is None:           # in this process, on its own maps
            return [fn(self._maps[which], s, k, result_max, ln)
                    for s, k in pieces]
        futs = [self.pool.submit(worker_call, fn, which, s, k, result_max, ln)
                for s, k in pieces]
        return [f.result() for f in futs]

    def counts(self, sweeps, result_max: int, ln: str = "exact",
               which: str = "ws"):
        """[(start, n)] -> [(counts, bad)] in the same order."""
        out = []
        for start, n in sweeps:
            got = self._pieces(_counts, which, start, n, result_max,
                               ln)
            out.append((sum(c for c, _ in got), sum(b for _, b in got)))
        return out

    def vectors(self, start: int, n: int, result_max: int,
                ln: str = "exact", which: str = "ws") -> np.ndarray:
        """(n, result_max) result vectors of start .. start+n-1."""
        return np.concatenate(
            self._pieces(_vectors, which, start, n, result_max, ln))

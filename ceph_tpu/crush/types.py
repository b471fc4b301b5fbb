"""The in-memory CRUSH map model.

ref: src/crush/crush.h (struct crush_map, crush_bucket*, crush_rule) —
re-modeled as plain dataclasses. Weights are 16.16 fixed point
(0x10000 == 1.0) exactly as in the reference; bucket ids are negative,
device ids non-negative.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

# Bucket algorithms (ref: src/crush/crush.h enum crush_algorithm).
ALG_UNIFORM = 1
ALG_LIST = 2
ALG_TREE = 3
ALG_STRAW = 4
ALG_STRAW2 = 5

# Rule step ops (ref: src/crush/crush.h enum crush_opcodes).
OP_NOOP = 0
OP_TAKE = 1
OP_CHOOSE_FIRSTN = 2
OP_CHOOSE_INDEP = 3
OP_EMIT = 4
OP_CHOOSELEAF_FIRSTN = 6
OP_CHOOSELEAF_INDEP = 7
OP_SET_CHOOSE_TRIES = 8
OP_SET_CHOOSELEAF_TRIES = 9
OP_SET_CHOOSE_LOCAL_TRIES = 10
OP_SET_CHOOSE_LOCAL_FALLBACK_TRIES = 11
OP_SET_CHOOSELEAF_VARY_R = 12
OP_SET_CHOOSELEAF_STABLE = 13

OP_NAMES = {
    OP_TAKE: "take", OP_CHOOSE_FIRSTN: "choose firstn",
    OP_CHOOSE_INDEP: "choose indep", OP_EMIT: "emit",
    OP_CHOOSELEAF_FIRSTN: "chooseleaf firstn",
    OP_CHOOSELEAF_INDEP: "chooseleaf indep",
    OP_SET_CHOOSE_TRIES: "set_choose_tries",
    OP_SET_CHOOSELEAF_TRIES: "set_chooseleaf_tries",
    OP_SET_CHOOSE_LOCAL_TRIES: "set_choose_local_tries",
    OP_SET_CHOOSE_LOCAL_FALLBACK_TRIES: "set_choose_local_fallback_tries",
    OP_SET_CHOOSELEAF_VARY_R: "set_chooseleaf_vary_r",
    OP_SET_CHOOSELEAF_STABLE: "set_chooseleaf_stable",
}

# Sentinels (ref: src/crush/crush.h CRUSH_ITEM_NONE / CRUSH_ITEM_UNDEF).
ITEM_NONE = 0x7FFFFFFF
ITEM_UNDEF = 0x7FFFFFFE

WEIGHT_ONE = 0x10000  # 16.16 fixed point 1.0
# id of the compat weight-set in CrushMap.choose_args (ref:
# CrushWrapper::DEFAULT_CHOOSE_ARGS): the one `ceph osd crush
# weight-set create-compat` and the balancer's crush-compat mode keep
COMPAT_CHOOSE_ARGS = -1


@dataclass
class Bucket:
    """An interior node (ref: src/crush/crush.h struct crush_bucket).

    id: negative; type: positive hierarchy level (host/rack/...);
    items: child ids (devices >= 0 or buckets < 0);
    weights: per-item 16.16 weights (straw2/list use them; uniform uses
    item_weight for all).
    """

    id: int
    type: int
    alg: int = ALG_STRAW2
    hash: int = 0  # CRUSH_HASH_RJENKINS1
    items: list[int] = field(default_factory=list)
    weights: list[int] = field(default_factory=list)

    # straw(v1) only: per-item straw lengths scaled 16.16, computed by the
    # builder (ref: src/crush/builder.c crush_calc_straw); None until built.
    straws: list[int] | None = None
    # tree only: binary-tree node weights (ref: crush.h crush_bucket_tree
    # node_weights; items live at odd nodes 2i+1); None until built.
    node_weights: list[int] | None = None

    @property
    def size(self) -> int:
        return len(self.items)

    @property
    def weight(self) -> int:
        return sum(self.weights)

    @property
    def num_nodes(self) -> int:
        return len(self.node_weights) if self.node_weights else 0


@dataclass
class ChooseArg:
    """Per-bucket weight-set override (ref: src/crush/crush.h
    struct crush_choose_arg: weight_set[positions][size] + ids[size]).

    weight_set: one weight vector per replica position (16.16); the draw
    for replica slot p uses weight_set[min(p, positions-1)] (out-of-range
    positions clamp to the last set, ref: mapper.c get_choose_arg_weights).
    ids: optional substitute item ids fed to the straw2 hash.
    """

    weight_set: list[list[int]] = field(default_factory=list)
    ids: list[int] | None = None


@dataclass
class RuleStep:
    op: int
    arg1: int = 0
    arg2: int = 0


@dataclass
class Rule:
    """ref: src/crush/crush.h struct crush_rule (+rule mask min/max size)."""

    id: int
    steps: list[RuleStep] = field(default_factory=list)
    type: int = 1  # pool type this serves: 1=replicated, 3=erasure
    name: str = ""


@dataclass
class Tunables:
    """ref: src/crush/crush.h crush_map tunables; defaults = jewel profile
    (ref: src/crush/CrushWrapper.h set_tunables_jewel)."""

    choose_local_tries: int = 0
    choose_local_fallback_tries: int = 0
    choose_total_tries: int = 50
    chooseleaf_descend_once: int = 1
    chooseleaf_vary_r: int = 1
    chooseleaf_stable: int = 1

    @classmethod
    def legacy(cls) -> "Tunables":
        """Pre-bobtail behavior (ref: set_tunables_legacy)."""
        return cls(choose_local_tries=2, choose_local_fallback_tries=5,
                   choose_total_tries=19, chooseleaf_descend_once=0,
                   chooseleaf_vary_r=0, chooseleaf_stable=0)


@dataclass
class CrushMap:
    """ref: src/crush/crush.h struct crush_map + CrushWrapper name maps."""

    buckets: dict[int, Bucket] = field(default_factory=dict)  # id -> bucket
    rules: dict[int, Rule] = field(default_factory=dict)
    tunables: Tunables = field(default_factory=Tunables)
    max_devices: int = 0
    type_names: dict[int, str] = field(default_factory=lambda: {0: "osd"})
    bucket_names: dict[int, str] = field(default_factory=dict)
    device_classes: dict[int, str] = field(default_factory=dict)
    # Weight-sets (ref: src/crush/crush.h crush_choose_arg_map;
    # CrushWrapper choose_args): key (int id, -1 = the compat weight-set)
    # -> {bucket_id -> ChooseArg}. Only straw2 draws consult them.
    choose_args: dict[int, dict[int, "ChooseArg"]] = field(
        default_factory=dict)

    def bucket(self, item: int) -> Bucket:
        return self.buckets[item]

    def choose_args_with_fallback(self, key: int) -> int | None:
        """Which weight-set serves id ``key``: the set of that id, else
        the compat set (-1, upstream's DEFAULT_CHOOSE_ARGS), else none
        (ref: CrushWrapper::choose_args_get_with_fallback). ``key`` is
        a pool's id for the OSDMap and 0 for ``crushtool --test``
        (CrushTester::test calls ``do_rule(..., 0)``)."""
        if key in self.choose_args:
            return key
        if COMPAT_CHOOSE_ARGS in self.choose_args:
            return COMPAT_CHOOSE_ARGS
        return None

    def is_bucket(self, item: int) -> bool:
        return item < 0

    def item_type(self, item: int) -> int:
        """0 for devices, bucket.type for buckets."""
        return self.buckets[item].type if item < 0 else 0

    def max_bucket_size(self) -> int:
        return max((b.size for b in self.buckets.values()), default=0)

    def validate(self) -> None:
        for bid, b in self.buckets.items():
            if bid != b.id or bid >= 0:
                raise ValueError(f"bad bucket id {bid}")
            if len(b.items) != len(b.weights):
                raise ValueError(f"bucket {bid}: items/weights mismatch")
            for item in b.items:
                if item < 0 and item not in self.buckets:
                    raise ValueError(f"bucket {bid}: dangling child {item}")
                if item >= 0 and item >= self.max_devices:
                    raise ValueError(f"bucket {bid}: device {item} out of "
                                     f"range (max_devices={self.max_devices})")

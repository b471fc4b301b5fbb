"""Plain reference for a replicated pool: what every replica of an
object must hold, and which OSDs must hold it.

It imports nothing of the program. The bytes are the easy half: a
replicated pool stores the object whole, so each of the ``size`` copies
is the bytes written. The holders are ``OSDMap::object_locator_to_pg``
and ``_pg_to_up_acting_osds`` written out from upstream's sources:

    ps   = ceph_str_hash_rjenkins(name)                (ceph_hash.cc)
    pg   = ceph_stable_mod(ps, pg_num, pg_num_mask)    (rados.h)
    pps  = crush_hash32_2(ceph_stable_mod(ps, pgp_num, pgp_num_mask),
                          pool id)                     (HASHPSPOOL)
    osds = crush_do_rule(rule, pps, size, weights)     (crush_ref.py)

with every OSD up and in, no upmap and no pg_temp, which is the state a
run's pool is in (the driver checks that before it asks). The CRUSH map
comes as a plain description -- buckets with their items and weights,
the rule's steps, the tunables -- of the OSDMap the cluster holds; the
mapping itself is ``crush_ref.do_rule``, mapper.c as it stands.
"""

from __future__ import annotations

from . import crush_ref

U32 = 0xFFFFFFFF
GOLDEN = 0x9E3779B9
STEP_OPS = ("take", "choose_firstn", "chooseleaf_firstn", "emit")


def str_hash_rjenkins(data: bytes) -> int:
    """ceph_hash.cc ceph_str_hash_rjenkins: Jenkins' one-at-a-time mix
    over 12-byte blocks, the tail's bytes added by the fall-through
    switch, the length in c."""
    a = b = GOLDEN
    c = 0
    k, left = 0, len(data)

    def word(at: int, n: int = 4) -> int:
        return int.from_bytes(data[at:at + n], "little")

    while left >= 12:
        a = (a + word(k)) & U32
        b = (b + word(k + 4)) & U32
        c = (c + word(k + 8)) & U32
        a, b, c = crush_ref._mix(a, b, c)
        k, left = k + 12, left - 12
    c = (c + len(data)) & U32
    # the last 11 bytes: 0-3 into a, 4-7 into b, 8-10 into c above its
    # first byte, which holds the length
    a = (a + word(k, min(left, 4))) & U32
    if left > 4:
        b = (b + word(k + 4, min(left - 4, 4))) & U32
    if left > 8:
        c = (c + (word(k + 8, left - 8) << 8)) & U32
    return crush_ref._mix(a, b, c)[2]


def mask_of(n: int) -> int:
    """pg_pool_t::calc_pg_masks: 2^ceil(log2 n) - 1."""
    return (1 << (n - 1).bit_length()) - 1


def stable_mod(x: int, b: int, bmask: int) -> int:
    """rados.h ceph_stable_mod."""
    return x & bmask if (x & bmask) < b else x & (bmask >> 1)


def crush_map(desc: dict) -> crush_ref.Map:
    """The description as ``crush_ref``'s map: ``buckets`` [{id, type,
    items, weights}], ``rule`` [[op, arg1, arg2]] with the ops of
    ``STEP_OPS``, ``tunables``, ``max_devices``. Straw2 only."""
    for b in desc["buckets"]:
        if b.get("alg", "straw2") != "straw2":
            raise ValueError(f"bucket {b['id']} is {b['alg']}, not straw2")
    buckets = [crush_ref.Bucket(b["id"], b["type"], b["items"],
                                b["weights"]) for b in desc["buckets"]]
    rule = []
    for op, a1, a2 in desc["rule"]:
        if op not in STEP_OPS:
            raise ValueError(f"rule step {op!r} is not in the reference")
        rule.append({"take": (op, a1), "emit": (op,)}.get(op, (op, a1, a2)))
    return crush_ref.Map(buckets, int(desc["max_devices"]), rule,
                         dict(crush_ref.JEWEL, **desc.get("tunables", {})))


class Pool:
    """A replicated pool of the description ``{id, pg_num, pgp_num,
    size, hashpspool}`` on the CRUSH map ``desc``; ``weights`` is the
    OSDMap's in/out vector (16.16, all in when left out)."""

    def __init__(self, pool: dict, crush_desc: dict, weights=None):
        self.id = int(pool["id"])
        self.pg_num = int(pool["pg_num"])
        self.pgp_num = int(pool.get("pgp_num", self.pg_num))
        self.size = int(pool["size"])
        self.hashpspool = bool(pool.get("hashpspool", True))
        self.map = crush_map(crush_desc)
        self.weights = None if weights is None else [int(w) for w in weights]
        self._acting = {}

    def pg_of(self, name: str) -> int:
        """The object's PG (the seed after folding onto pg_num)."""
        ps = str_hash_rjenkins(name.encode())
        return stable_mod(ps, self.pg_num, mask_of(self.pg_num))

    def pgid(self, name: str) -> str:
        return f"{self.id}.{self.pg_of(name):x}"

    def acting_of_pg(self, pg: int) -> list[int]:
        """The OSDs of the PG, primary first."""
        if pg not in self._acting:
            folded = stable_mod(pg, self.pgp_num, mask_of(self.pgp_num))
            pps = crush_ref.hash32_2(folded, self.id) if self.hashpspool \
                else (folded + self.id) & U32
            osds = crush_ref.do_rule(self.map, pps, self.size, self.weights)
            self._acting[pg] = [o for o in osds if o != crush_ref.ITEM_NONE]
        return self._acting[pg]

    def acting(self, name: str) -> list[int]:
        return self.acting_of_pg(self.pg_of(name))


def replicas(payload: bytes, size: int) -> list[bytes]:
    """What each of the ``size`` copies must hold."""
    return [payload] * size

"""Peaks of one chip, keyed by ``jax.devices()[0].device_kind``, and the
least time the chip could take for a piece of work.

Copied from ``ceph_tpu/utils/roofline.py`` (the original stays in the
program for its own benches; see PERF.md, Open questions). Source of
every number: Google Cloud TPU documentation, the "System architecture"
page of each generation (cloud.google.com/tpu/docs/v5e, /v5p, /v4,
/v6e): HBM bandwidth, int8 TOPS (multiply-accumulates are half of
that; v4 publishes no int8 rate, its bf16 figure stands in) and HBM
capacity. A v5e reports itself as "TPU v5 lite" (seen on the chip,
PR 22).

No public page gives a VPU (vector unit) integer rate for any of them,
so there is no ``vpu_int_ops_per_s`` here and nothing that would need
one is reported (``crush_roofline_pct``: PERF.md, Open questions).

A ``device_kind`` that is not in the table is an error, never a
default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    name: str
    hbm_bytes_per_s: float
    int8_macs_per_s: float
    hbm_bytes: float
    source: str


_DOC = "cloud.google.com/tpu/docs/{} (System architecture)"
PEAKS = {
    "TPU v5 lite": Peaks("TPU v5e", 819e9, 393e12 / 2, 16 * 2**30,
                         _DOC.format("v5e")),
    "TPU v5e": Peaks("TPU v5e", 819e9, 393e12 / 2, 16 * 2**30,
                     _DOC.format("v5e")),
    "TPU v5": Peaks("TPU v5p", 2765e9, 918e12 / 2, 95 * 2**30,
                    _DOC.format("v5p")),
    "TPU v4": Peaks("TPU v4", 1228e9, 275e12 / 2, 32 * 2**30,
                    _DOC.format("v4")),
    "TPU v6 lite": Peaks("TPU v6e", 1640e9, 1836e12 / 2, 32 * 2**30,
                         _DOC.format("v6e")),
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r}: add a row with "
            f"its public source to benchmark/harness/peaks.py") from None


def encode_bound(k: int, m: int, peaks: Peaks) -> tuple[float, str]:
    """Most encode INPUT bytes a second, and which peak sets it.

    HBM: every input byte is read once and m/k bytes of parity are
    written for it; all else could stay in VMEM. MXU: the bit-plane
    product of an (8m) x (8k) matrix does 64 m multiply-accumulates an
    input byte."""
    hbm = peaks.hbm_bytes_per_s / (1.0 + m / k)
    mxu = peaks.int8_macs_per_s / (64.0 * m)
    return (hbm, "hbm") if hbm <= mxu else (mxu, "mxu")


def decode_bound(n_erased: int, n_read: int,
                 peaks: Peaks) -> tuple[float, str]:
    """Most decode READ bytes a second (bytes of the chunks gathered),
    and which peak sets it: 1 + n_erased/n_read bytes of HBM traffic
    and 64 n_erased multiply-accumulates a read byte."""
    n_erased = max(n_erased, 1)
    hbm = peaks.hbm_bytes_per_s / (1.0 + n_erased / n_read)
    mxu = peaks.int8_macs_per_s / (64.0 * n_erased)
    return (hbm, "hbm") if hbm <= mxu else (mxu, "mxu")


# -- CRUSH: the draws a rule on a map requires ------------------------------

# crush_hash32_rjenkins1_3: the seed xor (3), five crush_hashmix rounds
# of nine lines, each two subtractions, a shift and an xor (4).
RJENKINS3_INT_OPS = 3 + 5 * 9 * 4


def straw2_draws_per_mapping(widths: list[int], num_rep: int) -> int:
    """straw2 draws one mapping needs when nothing collides: every
    replica descends once through buckets of these widths (root to
    leaf), one draw an item. Retries after a collision add to it; the
    least the rule requires does not count them."""
    return num_rep * sum(widths)

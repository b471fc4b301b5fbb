"""Physical performance bounds per device — the honesty guard for benchmarks.

Round 1's headline number (9,317 GiB/s) was physically impossible on a
v5e chip; the timing loop measured dispatch, not execution. Every
benchmark now (a) anchors timing with a device-side reduction
read back to host, and (b) passes its result through :func:`check`, which
refuses to report a rate above the device's roofline.

Bounds are deliberately *optimistic* (best-case fusion, minimum possible
HBM traffic): a measurement above them is certainly wrong; a measurement
below them is not thereby certified, just possible.

ref: the reference harness (src/test/erasure-code/ceph_erasure_code_benchmark.cc
ErasureCodeBench::run) has no such guard because wall-clock timing of a
synchronous C++ loop cannot overshoot; an asynchronous device can.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DeviceSpec:
    name: str
    hbm_bytes_per_s: float      # peak HBM bandwidth
    int8_macs_per_s: float      # peak MXU int8 multiply-accumulates/s
    hbm_bytes: float            # capacity


# Peaks per chip, keyed by ``jax.devices()[0].device_kind``. Source:
# Google Cloud TPU documentation, the "System architecture" page of
# each generation (cloud.google.com/tpu/docs/v5e, /v5p, /v4, /v6e):
# HBM bandwidth, int8 TOPS (MACs = OPS/2; v4 has no int8 rate, its
# bf16 figure stands in) and HBM capacity. A v5e reports itself as
# "TPU v5 lite" (confirmed on the chip, PR 22).
_SPECS = {
    "TPU v5 lite": DeviceSpec("TPU v5e", 819e9, 393e12 / 2, 16 * 2**30),
    "TPU v5e": DeviceSpec("TPU v5e", 819e9, 393e12 / 2, 16 * 2**30),
    "TPU v5": DeviceSpec("TPU v5p", 2765e9, 918e12 / 2, 95 * 2**30),
    "TPU v4": DeviceSpec("TPU v4", 1228e9, 275e12 / 2, 32 * 2**30),
    "TPU v6 lite": DeviceSpec("TPU v6e", 1640e9, 1836e12 / 2, 32 * 2**30),
}


def device_spec(device_kind: str | None = None) -> DeviceSpec | None:
    """Spec for the current (or named) device; None when its
    ``device_kind`` is not in the table. On the CPU that means no guard
    (wall-clock there is synchronous); a measuring path on a chip
    treats None as an error (``chip_smoke.py`` does), never as a
    default."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return _SPECS.get(device_kind)


def encode_bound(k: int, m: int, spec: DeviceSpec) -> float:
    """Upper bound on encode *input* bytes/s for the (8m)x(8k) bit-matmul.

    HBM: minimum traffic per input byte is 1 (read data) + m/k (write
    parity); everything else could in principle stay in VMEM.
    MXU: the bit-plane product does (8m)*(8k) MACs per k input bytes
    = 64*m MACs per input byte.
    """
    hbm = spec.hbm_bytes_per_s / (1.0 + m / k)
    mxu = spec.int8_macs_per_s / (64.0 * m)
    return min(hbm, mxu)


def decode_bound(n_erased: int, n_read: int, spec: DeviceSpec) -> float:
    """Upper bound on decode *read* bytes/s (the benchmark's headline
    decode unit: chunk bytes actually read).

    The decode kernel is an (8*n_erased) x (8*n_read) bit-matmul over the
    read planes: 64*n_erased MACs per read byte; minimum HBM traffic per
    read byte is 1 (read) + n_erased/n_read (write reconstructions).
    """
    n_erased = max(n_erased, 1)
    hbm = spec.hbm_bytes_per_s / (1.0 + n_erased / n_read)
    mxu = spec.int8_macs_per_s / (64.0 * n_erased)
    return min(hbm, mxu)


def mfu(k: int, m: int, input_bytes_per_s: float, spec: DeviceSpec) -> float:
    """Fraction of MXU int8 peak the measured encode rate implies."""
    macs = 64.0 * m * input_bytes_per_s
    return macs / spec.int8_macs_per_s


class RooflineViolation(RuntimeError):
    pass


def check(measured_bytes_per_s: float, bound_bytes_per_s: float | None,
          what: str = "throughput") -> None:
    """Refuse to report a physically impossible number."""
    if bound_bytes_per_s is None:
        return
    if measured_bytes_per_s > bound_bytes_per_s * 1.02:  # 2% timer slack
        raise RooflineViolation(
            f"measured {what} {measured_bytes_per_s / 2**30:.1f} GiB/s exceeds "
            f"the device roofline {bound_bytes_per_s / 2**30:.1f} GiB/s — the "
            f"timing loop is not measuring execution; refusing to report it")

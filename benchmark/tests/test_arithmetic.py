"""Roofline arithmetic against hand-worked values, the percentile rule
and the spread."""

import pytest

from harness import peaks, stats

V5E = peaks.peaks_for("TPU v5 lite")
GIB = 2 ** 30


def test_v5e_peaks_and_source():
    assert V5E.hbm_bytes_per_s == 819e9
    assert V5E.int8_macs_per_s == 393e12 / 2
    assert "cloud.google.com/tpu/docs/v5e" in V5E.source
    assert peaks.peaks_for("TPU v5e") == V5E


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_encode_bound_8_3_on_a_v5e_is_hbm():
    rate, which = peaks.encode_bound(8, 3, V5E)
    assert which == "hbm"
    assert rate == pytest.approx(819e9 / (1 + 3 / 8))
    assert rate / GIB == pytest.approx(554.7, abs=0.05)
    # the MXU would allow 196.5e12 / 192 = 1023 GB/s
    assert 393e12 / 2 / (64 * 3) > rate


def test_decode_bound_1_of_8_on_a_v5e_is_hbm():
    rate, which = peaks.decode_bound(1, 8, V5E)
    assert which == "hbm"
    assert rate == pytest.approx(819e9 / (1 + 1 / 8))
    assert rate / GIB == pytest.approx(678.0, abs=0.05)


def test_mxu_bound_takes_over_for_many_parities():
    rate, which = peaks.encode_bound(8, 8, V5E)
    assert which == "mxu"
    assert rate == pytest.approx(393e12 / 2 / 512)


def test_draws_on_the_10k_map():
    # 20 racks, 32 hosts a rack, 16 OSDs a host, three replicas
    assert peaks.straw2_draws_per_mapping([20, 32, 16], 3) == 204
    assert peaks.RJENKINS3_INT_OPS == 183


def test_p95_is_the_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 95) == 3
    assert stats.percentile(list(range(1, 21)), 95) == 19
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_iqr_share_is_the_drivers():
    vals = [10.0, 10.2, 9.9, 10.1, 10.0, 9.8]
    # statistics.quantiles(n=4): q1 = 9.875, q3 = 10.125
    assert stats.iqr_share(vals) == pytest.approx(0.25 / 10.0)

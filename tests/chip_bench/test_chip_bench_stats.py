"""The benchmark's own arithmetic and its table of peaks."""

from __future__ import annotations

import math

import numpy as np
import pytest

from benchmarks.chip import kernel_bytes, stats


def test_percentile_is_linear_like_numpy():
    xs = np.random.default_rng(3).exponential(size=101)
    for p in (0, 50, 95, 100):
        assert stats.percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    assert stats.percentile([], 95) == 0.0


def test_unanswered_requests_are_infinitely_late():
    lat = [10.0] * 97 + [math.inf] * 3  # 3% never answered
    assert stats.percentile(lat, 50) == 10.0
    assert math.isinf(stats.percentile(lat, 99))
    assert stats.percentile(lat, 95) == 10.0


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]) == pytest.approx((5.25 - 1.75) / 3.5)


def test_peaks_are_keyed_by_device_kind():
    v5e = stats.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        stats.peaks("cpu")


@pytest.mark.parametrize("n_p, d_p, expect", [
    # network 1·64·2048·4 + mask 64·2048 + domain 2·2048·4 + seed 64·4 + 8
    (64, 32, 524_288 + 131_072 + 16_384 + 256 + 8),
    # network 2·104·4160·4 + mask 104·4160 + domain 2·4160·4 + seed 104·4 + 8
    (104, 40, 3_461_120 + 432_640 + 33_280 + 416 + 8),
])
def test_packed_kernel_bytes_by_hand(n_p, d_p, expect):
    assert kernel_bytes.fixpoint_row_bytes("packed", n_p, d_p) == expect


def test_dense_kernel_bytes_and_unknown_encoding():
    lanes = 64 * 32
    assert kernel_bytes.fixpoint_row_bytes("dense", 64, 32) == (
        lanes * lanes + 64 * lanes + 2 * lanes * 4 + 64 * 4 + 8)
    with pytest.raises(ValueError):
        kernel_bytes.fixpoint_row_bytes("sparse", 64, 32)

"""Byte counts and peaks behind the fold's roofline share."""
import json
import os

import pytest

import _bench_paths as bp

import roofline


@pytest.mark.parametrize("events,least", [
    (1, 9), (8192, 73_728), (131_072, 1_179_648)])
def test_fold_bytes_per_event(events, least):
    # value (f32) + cell (i32) + mask (bool), each read once.
    assert roofline.FOLD_BYTES_PER_EVENT == 9
    assert roofline.fold_bytes(events) == least


def test_fold_share_at_v5e_bandwidth():
    with open(os.path.join(bp.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    bw = roofline.peak(peaks, "TPU v5 lite")["hbm_bytes_per_s"]
    assert bw == 819e9
    least = roofline.fold_bytes(8192) / bw
    # A fold of 8,192 events in 0.3 ms reads at 0.03% of the roofline.
    assert roofline.share_pct(least, 3e-4) == pytest.approx(
        100 * 73_728 / 819e9 / 3e-4)
    assert roofline.share_pct(least, least) == 100.0


def test_unknown_device_is_an_error():
    with open(os.path.join(bp.BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak(peaks, "TPU v9 imaginary")


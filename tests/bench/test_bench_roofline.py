"""The benchmark's roofline cost function and peaks table."""
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import roofline

DET = {"patch": 7, "sobel_size": 5, "window_size": 5}
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def test_event_cost_hand_worked():
    ops, nbytes = roofline.event_cost(7)
    # 8 SAE neighbours x 3 + 49 TOS pixels x 3
    assert ops == 24 + 147
    # inputs 13, SAE 9x4 read + 4 write, TOS 49 read + 49 write,
    # LUT read 4, outputs 5
    assert nbytes == 13 + 36 + 4 + 98 + 4 + 5 == 160


def test_refresh_cost_hand_worked():
    ops, nbytes = roofline.refresh_cost(2, 3, 5, 5)
    px = 6
    # divide 1, Sobel 2 x 20 non-zero taps x 2, products 3, box sums
    # 3 x 25 x 2, det - k tr^2 5
    assert ops == px * (1 + 80 + 3 + 150 + 5)
    assert nbytes == px * 5


def test_least_time_of_a_tiny_round():
    # one round of 3 lanes: 2 lanes with a 256-event chunk, the second of
    # which is due a refresh (its chunk index 3 hits lut_every 4)
    refreshes = roofline.due_refreshes([1, 3], [1, 1], 4)
    assert refreshes == 1
    t = roofline.least_time_s(512, refreshes, DET, 2, 3, PEAK)
    e_ops, e_bytes = roofline.event_cost(7)
    r_ops, r_bytes = roofline.refresh_cost(2, 3, 5, 5)
    assert t == max((512 * e_bytes + r_bytes) / 1e9,
                    (512 * e_ops + r_ops) / 1e12)


@pytest.mark.parametrize("first,n,want", [
    ([0], [3], 0),          # chunks 0..2: none due
    ([0], [4], 1),          # chunk 3 refreshes
    ([2, 5], [2, 6], 2),    # chunk 3, then chunk 7 of 5..10
])
def test_refreshes_only_for_lanes_due(first, n, want):
    got = roofline.due_refreshes(first, n, 4)
    brute = sum(1 for f, k in zip(first, n) for c in range(f, f + k)
                if (c + 1) % 4 == 0)
    assert got == brute == want


def test_unknown_device_kind_raises(tmp_path):
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"TPU v5 lite": PEAK}))
    assert roofline.peaks("TPU v5 lite", p) == PEAK
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary", p)


def test_peaks_table_has_v5e():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9


def test_share_over_one_is_refused():
    assert roofline.share(0.5, 1.0) == 0.5
    with pytest.raises(ValueError):
        roofline.share(1.5, 1.0)
    with pytest.raises(ValueError):
        roofline.share(0.1, 0.0)

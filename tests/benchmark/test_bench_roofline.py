"""Lower-bound byte counts and the peak table."""
import pytest

from bench_cases import harness

import roofline  # noqa: E402  (on the path once bench_cases is imported)

BENCH = harness.load_benchmark()


def test_hand_count_at_a_tiny_shape():
    # 2 processes x 4 rings, payload of 3 words; 5 messages drained and 6
    # pushed: rings 8 * (16 + 4), receivers 2 * 5, messages 11 * (8 + 12)
    assert roofline.duct_window_bytes(2, 8, 3, 5, 6) == 160 + 10 + 220
    # commit: 8 rings * 12 + 2 * 6 pushes * 20
    assert roofline.duct_commit_bytes(8, 3, 6) == 96 + 240
    # window: per process 2 * (33 + 4 simels * 4 * (1 + 3) + 4 * 3 * 4)
    assert roofline.window_bytes(2, 8, 3, 4, 3, 5, 6) == (
        2 * 2 * (33 + 64 + 48) + 390)
    assert roofline.capacity_sweep_bytes(8, 16, 3) == 2 * 8 * 16 * 20


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_lower_bound_within_the_capacity_sweep(workload):
    c = harness.resolve(BENCH, workload).config
    n = c["processes"]
    R, C = 4 * n, c["buffer_capacity"]
    s = harness.shapes(harness.resolve(BENCH, workload), 16)
    # the most a window can drain and push: max_pops per ring, one push
    most = roofline.duct_window_bytes(n, R, s["L"], R * c["max_pops"], R)
    sweep = roofline.capacity_sweep_bytes(R, C, s["L"])
    assert most <= sweep
    assert roofline.duct_commit_bytes(R, s["L"], R * 8) <= sweep


def test_peaks_known_and_unknown():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_share_is_none_without_time():
    assert roofline.share(1.0, 0.0, 819e9) is None
    assert roofline.share(819e9, 2.0, 819e9) == 50.0

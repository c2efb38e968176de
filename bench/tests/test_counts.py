"""Shape-derived operation and byte counts, and the peak table."""
import pytest

from bench import counts


def test_peaks_v5e_and_unknown_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_explore_bytes_by_hand():
    # K=2, d=3, r_cap=K: 4 + 2 = 6 candidates per row
    per_row = (3 * 4 + 2 * 4 + 6 * 4 + 6 * 3 * 4 + 2 * 4 + 2 * 2 * 8)
    per_call = 10 * 2 * 4 + 10 * 2 * 4
    assert counts.explore_bytes(5, n=10, k=2, d=3) == 5 * per_row + per_call


def test_explore_bytes_mnist_block_is_the_candidate_gather():
    b = counts.explore_bytes(512, n=70_000, k=150, d=784)
    gather = 512 * 22_650 * 784 * 4
    assert gather < b < 1.02 * gather


def test_edge_step_bytes_by_hand():
    # 8 edges of 2 + 5 rows, s=2: the rows in and out, 7 ids and 5 masks
    # per edge, the rate; the size of y plays no part
    assert counts.edge_step_bytes(s=2, batch=8, negatives=5) == (
        2 * 8 * 7 * 2 * 4 + 8 * 7 * 4 + 8 * 5 * 4 + 4)


def test_roofline_share_takes_the_slower_bound():
    peak = counts.peaks("TPU v5 lite")
    share, bound = counts.roofline_share(1e-3, bytes_=819e9 * 5e-4,
                                         flops=197e12 * 1e-4, peak=peak)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(1e-3, bytes_=0.0,
                                         flops=197e12 * 1e-3, peak=peak)
    assert bound == "flops" and share == pytest.approx(100.0)
    with pytest.raises(ValueError):
        counts.roofline_share(0.0, bytes_=1.0, peak=peak)

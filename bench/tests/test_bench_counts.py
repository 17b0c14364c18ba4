"""The benchmark's yardstick, checked by hand at small sizes: operation
and byte counts, the peaks table and the data generator."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchkit import counts, data, peaks  # noqa: E402


def test_gram_counts_by_hand():
    # K=2: each observation adds 2K^2 + 2K = 8 + 4 = 12 FLOP per
    # orientation; 3 observations, 2 orientations
    assert counts.gram_flops(3, 2) == 72.0
    # ids, values, mask: 12 B per observation per orientation = 72;
    # fixed rows 5 x K x 4 B = 40; Gram + RHS 5 x (4 + 2) x 4 B = 120
    assert counts.gram_bytes(3, 5, 5, 2) == 72 + 40 + 120


def test_sweep_flops_by_hand():
    # K=3, 2 + 4 rows, 5 observations, 7 test entries
    k, nnz = 3, 5
    gram = 2 * nnz * (2 * 9 + 2 * 3)           # 240
    solve = 6 * (27 / 3 + 3 * 9)               # 216
    hyper = 2 * 6 * 9                          # 108
    noise, test = 2 * 3 * nnz, 2 * 3 * 7       # 30, 42
    assert counts.sweep_flops(2, 4, k, nnz, 7) == gram + solve + hyper \
        + noise + test


def test_topk_counts_by_hand():
    # B=2 users, S=3 samples, N=5 items, K=4, top 2
    assert counts.topk_flops(2, 3, 5, 4) == 2 * 3 * 5 * (8 + 3)
    stack, users, mask = 3 * 5 * 4 * 4, 2 * 3 * 4 * 4, 2 * 5 * 4
    answers = 2 * 2 * 12
    assert counts.topk_bytes(2, 3, 5, 4, 2) == stack + users + mask \
        + answers


def test_real_observations_are_counted_not_padding():
    # a padded layout: 3 rows padded to width 4, 5 real entries
    mask = np.array([[1, 1, 0, 0], [1, 0, 0, 0], [1, 1, 0, 0]], np.float32)
    assert counts.observations(mask) == 5
    assert counts.gram_flops(counts.observations(mask), 2) \
        < counts.gram_flops(mask.size, 2)


def test_least_time_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert counts.least_time(197e12, 0.0, p) == pytest.approx(1.0)
    assert counts.least_time(0.0, 819e9, p) == pytest.approx(1.0)
    assert counts.least_time(1e12, 819e9, p) == pytest.approx(1.0)


def test_the_bmf_chembl_share_counts():
    # the numbers PERF.md quotes for the 1/32 share
    k, rows, cols, nnz = 128, 32_768, 8_192, 32_768 * 64
    flops = counts.sweep_flops(rows, cols, k, nnz, rows * 8)
    assert 1.6e11 < flops < 1.8e11
    least = counts.least_time(counts.gram_flops(nnz, k),
                              counts.gram_bytes(nnz, rows + cols,
                                                rows + cols, k),
                              peaks.peaks_for("TPU v5 lite"))
    assert 3.3e-3 < least < 3.5e-3      # bound by writing the Grams
    topk = counts.least_time(counts.topk_flops(8, 100, cols, k),
                             counts.topk_bytes(8, 100, cols, k, 100),
                             peaks.peaks_for("TPU v5 lite"))
    assert 0.50e-3 < topk < 0.52e-3     # reading the item stack once


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_fixed_degree_matches_the_program_generator():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "src"))
    from repro.data.synthetic import fixed_degree
    mat, (ti, tj, tv), cols = fixed_degree(3, 64, 32, 8)
    p = data.fixed_degree(3, 64, 32, 8)
    assert np.array_equal(cols, p.cols)
    assert np.array_equal(ti, p.ti) and np.array_equal(tj, p.tj)
    assert np.allclose(tv, p.tv)
    assert len(p.v) == 64 * 8 and int(mat.nnz) == len(p.v)
    # same seed, same data; another seed, another
    again = data.fixed_degree(3, 64, 32, 8)
    assert np.array_equal(again.v, p.v)
    assert not np.array_equal(data.fixed_degree(4, 64, 32, 8).v, p.v)

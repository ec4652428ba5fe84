"""Work and peak functions against counts made by hand."""
import numpy as np
import pytest

from chipbench import work


def test_spmm_work_counts_distinct_rows_and_valid_slots():
    # two valid samples and one padding sample; row 7 is touched twice,
    # padding slots point at rows 0 and 9, which are no work
    feat_idx = np.array([[7, 3, 0], [7, 5, 9], [1, 2, 4]])
    feat_mask = np.array([[1, 1, 0], [1, 1, 0], [1, 1, 1]], bool)
    sample_mask = np.array([1, 1, 0], bool)
    flops, bytes_ = work.spmm_work(feat_idx, feat_mask, sample_mask, hidden=8)
    assert flops == 2 * 4 * 8                     # 4 valid nnz
    assert bytes_ == 3 * 8 * 4 + 4 * (4 + 4) + 2 * 8 * 4  # rows 3, 5, 7


@pytest.mark.parametrize("replicas,momentum,reads", [
    (4, True, 6),    # vmap: four replicas, the global and the previous global
    (1, False, 1),   # sharded: each chip merges its own replica
])
def test_weighted_merge_bytes(replicas, momentum, reads):
    n = 1000
    assert work.weighted_merge_bytes(n, replicas, momentum) == (reads + 1) * n * 4


def test_model_flops_per_sample():
    h, c = 128, 670091
    assert work.model_flops_per_sample(76, h, c) == 6 * h * c + 4 * 76 * h
    np.testing.assert_array_equal(
        work.model_flops_per_sample(np.array([0, 10]), 2, 3), [36.0, 116.0])


def test_peaks_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert work.least_seconds(197e12, 0.0, "TPU v5 lite") == 1.0
    assert work.least_seconds(0.0, 819e9, "TPU v5 lite") == 1.0
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")

"""The benchmark's generator at published widths: the mean nnz within 5%
of the published mean, the padded width and the valid-slot share."""
import json
import os

import numpy as np
import pytest

from chipbench import synth

from conftest import ROOT


@pytest.mark.parametrize("name,k", [("xml-amazon-670k", 256),
                                    ("xml-delicious-200k", 1024)])
def test_published_statistics(name, k):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        c = json.load(f)
    csr = synth.make_xml_csr(4096, c["n_features"], c["n_classes"], c["avg_nnz"],
                             c["avg_labels"], c["nnz_sigma"], seed=2**33 + 1)
    got_k, _ = synth.slot_widths(csr)
    s = synth.stats(csr, got_k)
    assert abs(s["mean_nnz"] / c["avg_nnz"] - 1) < 0.05
    assert abs(s["mean_labels"] / c["avg_labels"] - 1) < 0.05
    assert got_k == k
    assert 0.25 < s["valid_slot_share"] < 0.35
    ind = csr["indices"]
    for i in range(0, 4096, 97):   # ids ascending and distinct within a row
        row = ind[csr["indptr"][i]:csr["indptr"][i + 1]]
        assert (np.diff(row) > 0).all()
    assert ind.max() < c["n_features"] and csr["labels"].max() < c["n_classes"]


def test_same_seed_same_data_and_split():
    a = synth.make_xml_csr(512, 5000, 300, 20, 3, 0.5, seed=7)
    b = synth.make_xml_csr(512, 5000, 300, 20, 3, 0.5, seed=7)
    c = synth.make_xml_csr(512, 5000, 300, 20, 3, 0.5, seed=8)
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["indices"][:100], c["indices"][:100])
    tr, te = synth.split(a, 0.25, seed=3)
    assert len(tr["indptr"]) - 1 == 384 and len(te["indptr"]) - 1 == 128
    assert len(tr["indices"]) + len(te["indices"]) == len(a["indices"])


def test_zipf_sampler_is_the_inverse_cdf():
    n = 10007
    draw = synth._zipf_sampler(n)
    cdf = np.cumsum(1.0 / np.arange(1, n + 1) ** synth.ZIPF_EXPONENT)
    cdf /= cdf[-1]
    u = np.random.default_rng(0).random(200_000)
    u[:2] = [0.0, cdf[5]]
    np.testing.assert_array_equal(
        draw(u), np.minimum(np.searchsorted(cdf, u, side="right"), n - 1))

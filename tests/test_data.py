"""Data pipeline: synthetic XML stats, libSVM roundtrip, batcher/provider
invariants (hypothesis where useful)."""
import os

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.data.batcher import SampleStream, SparseBatcher
from repro.data.libsvm import read_libsvm, write_libsvm
from repro.data.providers import SparseProvider, TokenProvider
from repro.data.sparse import subset, train_test_split
from repro.data.xml_synth import make_paper_like, make_xml_dataset


@pytest.fixture(scope="module")
def ds():
    return make_xml_dataset(n_samples=256, n_features=512, n_classes=64, avg_nnz=24, seed=0)


class TestSynth:
    def test_shapes_and_stats(self, ds):
        assert ds.n_samples == 256
        assert ds.avg_nnz() > 10
        assert ds.avg_labels() >= 1
        # nnz varies across samples (the paper's heterogeneity source)
        nnz = np.diff(ds.indptr)
        assert nnz.std() > 2

    def test_primary_label_first(self, ds):
        for i in range(20):
            _, _, lab = ds.sample(i)
            assert len(lab) >= 1

    def test_rows_are_sorted_unique_and_in_range(self, ds):
        for i in range(ds.n_samples):
            feats, vals, lab = ds.sample(i)
            assert np.all(np.diff(feats) > 0)          # unique, ascending
            assert feats.min() >= 0 and feats.max() < ds.n_features
            assert np.all(vals > 0)
            assert len(np.unique(lab)) == len(lab)      # no repeated label
            assert np.all(np.diff(lab[1:]) > 0)         # companions sorted
            assert lab.max() < ds.n_classes

    def test_published_widths_keep_the_distribution(self):
        """Amazon-670K widths generate vectorized: log-normal nnz around the
        published mean, Zipf-biased feature ids, correlated label sets."""
        from repro.data.xml_synth import AMAZON_670K

        d = make_xml_dataset(n_samples=4096, seed=1, **AMAZON_670K)
        assert (d.n_features, d.n_classes) == (135_909, 670_091)
        assert 0.85 * 76 < d.avg_nnz() < 1.15 * 76
        nnz = np.diff(d.indptr)
        assert nnz.std() > 20                 # heavy per-sample variation
        # Zipf bias: the lowest 1% of feature ids draw far more than 1%
        assert np.mean(d.indices < d.n_features // 100) > 0.2
        assert 3 < d.avg_labels() < 6

    def test_paper_like_descriptors(self):
        d = make_paper_like("amazon-670k", scale=0.002, n_samples=64)
        assert d.n_classes >= 64
        d2 = make_paper_like("delicious-200k", scale=0.002, n_samples=64)
        assert d2.avg_nnz() > d.avg_nnz()  # delicious is denser (302 vs 76)

    def test_split_preserves_structure(self, ds):
        tr, te = train_test_split(ds, 0.25, seed=1)
        assert tr.n_samples + te.n_samples == ds.n_samples
        assert tr.n_features == ds.n_features


class TestLibSVM:
    def test_roundtrip(self, tmp_path, ds):
        small = subset(ds, np.arange(32))
        path = os.path.join(tmp_path, "d.svm")
        write_libsvm(small, path)
        back = read_libsvm(path)
        assert back.n_samples == 32
        assert back.n_features == ds.n_features
        for i in range(32):
            ai, av, al = small.sample(i)
            bi, bv, bl = back.sample(i)
            np.testing.assert_array_equal(ai, bi)
            np.testing.assert_allclose(av, bv, rtol=1e-4)
            np.testing.assert_array_equal(al, bl)


class TestBatcher:
    def test_stream_covers_epoch(self):
        s = SampleStream(100, seed=0)
        ids = s.take(100)
        assert sorted(ids.tolist()) == list(range(100))

    def test_stream_reshuffles(self):
        s = SampleStream(50, seed=0)
        e1 = s.take(50)
        e2 = s.take(50)
        assert sorted(e2.tolist()) == list(range(50))
        assert not np.array_equal(e1, e2)

    @given(take=st.integers(1, 64), slots=st.integers(64, 128))
    @settings(max_examples=20, deadline=None)
    def test_padded_batch_masks(self, ds, take, slots):
        b = SparseBatcher(ds, seed=1)
        batch = b.next_batch(take, slots)
        assert batch.feat_idx.shape[0] == slots
        assert batch.n_valid == take
        # masked rows are all zero
        assert not batch.feat_mask[take:].any()
        assert not batch.sample_mask[take:].any()

    def test_pack_truncates_to_max_nnz(self, ds):
        b = SparseBatcher(ds, max_nnz=8, seed=0)
        batch = b.next_batch(4, 4)
        assert batch.feat_idx.shape[1] == 8
        assert batch.feat_mask.sum(axis=1).max() <= 8


class TestProviders:
    def test_sparse_provider_work_units(self, ds):
        p = SparseProvider.make(ds)
        payload = p.fetch(16, 32)
        assert p.work_units(payload) == payload.total_nnz
        stacked = p.stack([payload, p.empty(32)])
        assert stacked["feat_idx"].shape[0] == 2
        assert stacked["sample_mask"][1].sum() == 0

    def test_token_provider(self):
        p = TokenProvider.make(vocab_size=97, seq_len=16)
        payload = p.fetch(3, 8)
        assert payload["tokens"].shape == (8, 16)
        assert payload["sample_mask"].sum() == 3
        assert p.work_units(payload) == 3 * 16
        assert payload["tokens"].max() < 97

    def test_token_bigram_structure(self):
        """The synthetic corpus must be more predictable than uniform."""
        p = TokenProvider.make(vocab_size=64, seq_len=128, seed=0)
        toks = p.stream.sample(64, 128)
        # successor entropy given a token should be far below log2(64)
        follows = {}
        for row in toks:
            for a, b in zip(row[:-1], row[1:]):
                follows.setdefault(int(a), []).append(int(b))
        top1 = np.mean([
            max(np.bincount(v)) / len(v) for v in follows.values() if len(v) >= 20
        ])
        assert top1 > 0.1  # uniform would be ~1/64

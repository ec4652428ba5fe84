"""Synthetic extreme multi-label (XML) dataset generator.

Mirrors the statistics of the paper's datasets (Table 1): very large sparse
feature/label spaces, power-law non-zero counts per sample, and a learnable
structure (class-prototype mixture) so accuracy curves are meaningful.

Generation model:
  * each class c has a prototype of ``proto_sz`` feature ids drawn Zipf-like
    from the feature space;
  * a sample picks a primary class, takes a noisy subset of its prototype,
    adds background-noise features, and tags ``~avg_labels`` correlated
    classes as its label set (primary class first).

The per-sample nnz is drawn from a log-normal — matching the paper's
observation that "the number of non-zero features varies significantly among
the training samples", the second source of heterogeneity.

Every draw is vectorized over classes or samples (no per-sample Python
loop), so the published widths (Amazon-670K: 670,091 classes) generate in
seconds. Zipf draws are one inverse-CDF lookup each; a prototype is drawn
with replacement and a sample's features are de-duplicated, so a class's
head features may repeat in its prototype. Prototypes are drawn only for
classes that are some sample's primary class — no other draw reads them.
"""
from __future__ import annotations

import numpy as np

from .sparse import SparseDataset


def _ragged_positions(counts: np.ndarray) -> np.ndarray:
    """For ragged rows of the given lengths, each element's offset in its row."""
    starts = np.cumsum(counts) - counts
    return np.arange(int(counts.sum())) - np.repeat(starts, counts)


def _unique_per_row(rows: np.ndarray, cols: np.ndarray, n_cols: int, n_rows: int):
    """De-duplicate (row, col) pairs. Returns (indptr, cols) in CSR order:
    rows ascending, each row's cols ascending."""
    key = np.unique(rows.astype(np.int64) * n_cols + cols)
    counts = np.bincount(key // n_cols, minlength=n_rows)
    indptr = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
    return indptr, (key % n_cols).astype(np.int32)


def make_xml_dataset(
    n_samples: int = 2048,
    n_features: int = 4096,
    n_classes: int = 512,
    avg_nnz: int = 64,
    nnz_sigma: float = 0.5,
    avg_labels: int = 3,
    proto_sz: int = 96,
    noise_frac: float = 0.2,
    seed: int = 0,
) -> SparseDataset:
    rng = np.random.default_rng(seed)

    # Zipf weights over feature ids; draws are inverse-CDF lookups
    zipf_cdf = np.cumsum(1.0 / (np.arange(1, n_features + 1) ** 0.8))
    zipf_cdf /= zipf_cdf[-1]

    def zipf(size):
        return np.minimum(
            np.searchsorted(zipf_cdf, rng.random(size), side="right"),
            n_features - 1,
        ).astype(np.int32)

    # label co-occurrence: each class has a fixed set of companion classes
    n_comp = max(1, avg_labels)
    companions = rng.integers(0, n_classes, size=(n_classes, n_comp))
    primary = rng.integers(0, n_classes, size=n_samples)
    # class prototypes: proto_sz Zipf-biased feature ids per class, drawn
    # only for the classes some sample has as its primary class
    used, proto_of = np.unique(primary, return_inverse=True)
    protos = zipf((len(used), proto_sz))
    nnz = np.clip(
        rng.lognormal(np.log(avg_nnz), nnz_sigma, size=n_samples), 4, 4 * avg_nnz
    ).astype(np.int64)
    n_noise = (nnz * noise_frac).astype(np.int64)
    n_proto = np.minimum(nnz - n_noise, proto_sz)

    # features: a random n_proto-subset of the primary class's prototype
    # (the first n_proto of a random permutation) plus Zipf background noise
    perm = np.argsort(rng.random((n_samples, proto_sz)), axis=1)
    take = np.arange(proto_sz)[None, :] < n_proto[:, None]
    proto_feats = protos[proto_of[:, None], perm][take]
    proto_rows = np.repeat(np.arange(n_samples), n_proto)
    noise_feats = zipf(int(n_noise.sum()))
    noise_rows = np.repeat(np.arange(n_samples), n_noise)
    indptr, indices = _unique_per_row(
        np.concatenate([proto_rows, noise_rows]),
        np.concatenate([proto_feats, noise_feats]),
        n_features, n_samples,
    )
    values = rng.gamma(2.0, 0.5, size=len(indices)).astype(np.float32)

    # labels: the primary class first (used for top-1 bookkeeping), then the
    # first n_lab - 1 companions of that class, de-duplicated and sorted
    n_lab = np.maximum(1, rng.poisson(avg_labels, size=n_samples))
    comp = companions[primary]
    keep = np.arange(n_comp)[None, :] < (n_lab - 1)[:, None]
    keep &= comp != primary[:, None]
    comp_ptr, comp_labels = _unique_per_row(
        np.nonzero(keep)[0], comp[keep], n_classes, n_samples
    )
    n_each = np.diff(comp_ptr) + 1
    label_ptr = np.concatenate(([0], np.cumsum(n_each))).astype(np.int64)
    labels = np.empty(int(label_ptr[-1]), np.int32)
    labels[label_ptr[:-1]] = primary
    is_comp = _ragged_positions(n_each) > 0
    labels[is_comp] = comp_labels

    return SparseDataset(
        n_features=n_features,
        n_classes=n_classes,
        indptr=indptr,
        indices=indices,
        values=values,
        label_ptr=label_ptr,
        labels=labels,
    )


# Paper-scale dataset descriptors (Table 1) — used by configs/benchmarks to
# instantiate scaled-down but statistically faithful stand-ins.
AMAZON_670K = dict(n_features=135_909, n_classes=670_091, avg_nnz=76, avg_labels=5)
DELICIOUS_200K = dict(n_features=782_585, n_classes=205_443, avg_nnz=302, avg_labels=75)


def make_paper_like(which: str, scale: float = 0.01, n_samples: int = 4096, seed: int = 0):
    """A scale-factor stand-in for Amazon-670k / Delicious-200k."""
    spec = {"amazon-670k": AMAZON_670K, "delicious-200k": DELICIOUS_200K}[which]
    return make_xml_dataset(
        n_samples=n_samples,
        n_features=max(256, int(spec["n_features"] * scale)),
        n_classes=max(64, int(spec["n_classes"] * scale)),
        avg_nnz=min(spec["avg_nnz"], 128),
        avg_labels=min(spec["avg_labels"], 16),
        seed=seed,
    )

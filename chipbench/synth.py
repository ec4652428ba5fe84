"""The benchmark's own synthetic XML data: a copy of the program's generator
(``repro.data.xml_synth.make_xml_dataset``), kept here so that later changes
to the program cannot move the yardstick.

What is kept from the original: Zipf(0.8) feature ids, a per-class
prototype from which a sample takes a subset, 20% Zipf background noise,
de-duplicated features, Gamma(2, 0.5) values, uniform primary classes, and
Poisson label counts filled with companions fixed per class.

What differs, so that the mean nnz per sample is the published one:

* the prototype cap is lifted. A sample's target nnz is drawn from a
  log-normal whose *mean* (not median) is the configuration's ``avg_nnz``,
  and the prototype is as long as the largest share a sample can take;
* a prototype is not stored: feature ``j`` of class ``c`` is a Zipf draw
  keyed by a hash of ``(seed, c, j)``, so only the entries samples use are
  ever drawn, and a sample takes a distinct set of prototype positions by
  an odd stride through the power-of-two prototype;
* features lost to de-duplication are topped up with fresh noise draws
  until every sample holds exactly its target count.
"""
from __future__ import annotations

import numpy as np

NOISE_FRAC = 0.2
ZIPF_EXPONENT = 0.8
MAX_TOPUP_ROUNDS = 32


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _hash_uniform(keys: np.ndarray, salt: int) -> np.ndarray:
    """Deterministic uniform [0, 1) per uint64 key."""
    with np.errstate(over="ignore"):
        h = _splitmix64(keys.astype(np.uint64) ^ np.uint64(salt))
    return (h >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))


def _ragged_positions(counts: np.ndarray) -> np.ndarray:
    starts = np.cumsum(counts) - counts
    return np.arange(int(counts.sum())) - np.repeat(starts, counts)


def _pow2_at_least(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def _zipf_sampler(n: int):
    """Inverse-CDF sampler of Zipf(ZIPF_EXPONENT) over ids [0, n).

    Equal to ``searchsorted(cdf, u, side="right")``, clipped to n - 1, but a
    guide table over [0, 1) gives each draw a start at most a few ids below
    its answer, so a draw costs a few steps, not a binary search."""
    cdf = np.cumsum(1.0 / (np.arange(1, n + 1) ** ZIPF_EXPONENT))
    cdf /= cdf[-1]
    bins = 1 << 20
    guide = np.searchsorted(cdf, np.arange(bins) / bins, side="right")

    def draw(u: np.ndarray) -> np.ndarray:
        i = guide[np.minimum((u * bins).astype(np.int64), bins - 1)]
        active = np.nonzero(cdf[np.minimum(i, n - 1)] <= u)[0]
        while len(active):
            i[active] += 1
            ia = i[active]
            active = active[(ia < n) & (cdf[np.minimum(ia, n - 1)] <= u[active])]
        return np.minimum(i, n - 1).astype(np.int64)

    return draw


def make_xml_csr(
    n_samples: int,
    n_features: int,
    n_classes: int,
    avg_nnz: float,
    avg_labels: float,
    nnz_sigma: float,
    seed: int,
) -> dict:
    """Generate the data set as CSR arrays (numpy only).

    Returns ``indptr, indices, values, label_ptr, labels`` with each row's
    feature ids ascending and the primary class first in each label row.
    """
    rng = np.random.default_rng(seed)
    salt = int(rng.integers(0, 2**63))
    zipf_of = _zipf_sampler(n_features)
    primary = rng.integers(0, n_classes, size=n_samples)

    # target nnz: log-normal with mean avg_nnz
    mu = np.log(avg_nnz) - nnz_sigma**2 / 2
    target = np.clip(
        np.rint(rng.lognormal(mu, nnz_sigma, size=n_samples)),
        4, min(4 * avg_nnz, n_features),
    ).astype(np.int64)
    n_noise = (target * NOISE_FRAC).astype(np.int64)
    n_proto = target - n_noise
    proto_len = _pow2_at_least(int(n_proto.max()))

    # prototype subset: distinct positions (offset + j * odd stride) mod P
    offset = rng.integers(0, proto_len, size=n_samples)
    stride = 2 * rng.integers(0, proto_len // 2 + 1, size=n_samples) + 1
    rows = np.repeat(np.arange(n_samples, dtype=np.int64), n_proto)
    j = _ragged_positions(n_proto)
    pos = (np.repeat(offset, n_proto) + j * np.repeat(stride, n_proto)) % proto_len
    proto_key = np.repeat(primary, n_proto).astype(np.uint64) * np.uint64(
        proto_len
    ) + pos.astype(np.uint64)
    proto_feats = zipf_of(_hash_uniform(proto_key, salt))
    noise_rows = np.repeat(np.arange(n_samples, dtype=np.int64), n_noise)
    noise_feats = zipf_of(rng.random(int(n_noise.sum())))
    keys = np.unique(
        np.concatenate([rows, noise_rows]) * n_features
        + np.concatenate([proto_feats, noise_feats])
    )

    # top up what de-duplication removed, with fresh noise draws
    extra = []
    have = np.bincount(keys // n_features, minlength=n_samples)
    for _ in range(MAX_TOPUP_ROUNDS):
        short = target - have
        if not short.any():
            break
        r = np.repeat(np.arange(n_samples, dtype=np.int64), short)
        cand = np.unique(r * n_features + zipf_of(rng.random(len(r))))
        idx = np.minimum(np.searchsorted(keys, cand), len(keys) - 1)
        cand = cand[keys[idx] != cand]
        if extra:
            cand = cand[~np.isin(cand, np.concatenate(extra))]
        # keep at most the shortfall of each row
        crow = cand // n_features
        rank = _ragged_positions(np.bincount(crow, minlength=n_samples))
        cand = cand[rank < short[crow]]
        extra.append(cand)
        have += np.bincount(cand // n_features, minlength=n_samples)
    if extra:
        keys = np.sort(np.concatenate([keys] + extra))
    counts = np.bincount(keys // n_features, minlength=n_samples)
    indptr = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
    indices = (keys % n_features).astype(np.int32)
    values = rng.gamma(2.0, 0.5, size=len(indices)).astype(np.float32)

    # labels: primary first, then n_lab - 1 companions of the class, de-duplicated
    # (companion k of class c is a hash of (c, k), so no table is stored)
    n_lab = np.maximum(1, rng.poisson(avg_labels, size=n_samples))
    n_comp = n_lab - 1
    crow = np.repeat(np.arange(n_samples, dtype=np.int64), n_comp)
    ck = np.repeat(primary, n_comp).astype(np.uint64) * np.uint64(1 << 20) + (
        _ragged_positions(n_comp).astype(np.uint64))
    comp = (_hash_uniform(ck, salt + 1) * n_classes).astype(np.int64)
    keep = comp != primary[crow]
    ckey = np.unique(crow[keep] * n_classes + comp[keep])
    n_each = np.bincount(ckey // n_classes, minlength=n_samples) + 1
    label_ptr = np.concatenate(([0], np.cumsum(n_each))).astype(np.int64)
    labels = np.empty(int(label_ptr[-1]), np.int32)
    labels[label_ptr[:-1]] = primary
    labels[_ragged_positions(n_each) > 0] = (ckey % n_classes).astype(np.int32)
    return dict(indptr=indptr, indices=indices, values=values,
                label_ptr=label_ptr, labels=labels)


def csr_rows(csr: dict, ids: np.ndarray) -> dict:
    """Row subset of CSR arrays, vectorized."""
    out = {}
    for ptr, fields in (("indptr", ("indices", "values")), ("label_ptr", ("labels",))):
        p = csr[ptr]
        starts, counts = p[ids], p[ids + 1] - p[ids]
        src = np.repeat(starts, counts) + _ragged_positions(counts)
        out[ptr] = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
        for f in fields:
            out[f] = csr[f][src]
    return out


def split(csr: dict, test_frac: float, seed: int) -> tuple[dict, dict]:
    """Random train/test split of the rows."""
    n = len(csr["indptr"]) - 1
    perm = np.random.default_rng(seed).permutation(n)
    n_test = int(n * test_frac)
    return csr_rows(csr, np.sort(perm[n_test:])), csr_rows(csr, np.sort(perm[:n_test]))


def slot_widths(csr: dict) -> tuple[int, int]:
    """Padded widths (K nnz slots, L label slots): K is the 98th-percentile
    nnz plus one, rounded up to a power of two (at least 8); L is the
    98th-percentile label count plus one."""
    nnz = np.diff(csr["indptr"])
    lab = np.diff(csr["label_ptr"])
    k = max(8, _pow2_at_least(int(np.quantile(nnz, 0.98)) + 1))
    return k, max(1, int(np.quantile(lab, 0.98)) + 1)


def stats(csr: dict, k: int) -> dict:
    nnz = np.diff(csr["indptr"])
    return {
        "samples": int(len(nnz)),
        "mean_nnz": float(nnz.mean()),
        "p98_nnz": float(np.quantile(nnz, 0.98)),
        "K": int(k),
        "valid_slot_share": float(np.minimum(nnz, k).sum() / (len(nnz) * k)),
        "truncated_share": float((nnz > k).mean()),
        "mean_labels": float(np.diff(csr["label_ptr"]).mean()),
    }

"""The sparse MLP of the paper's extreme multi-label experiments
(arXiv:2110.07029, the SLIDE testbed's network), as a model family of the
benchmark: everything the harness needs that is particular to this model.

A configuration names it with ``"family": "xml_mlp"`` and gives
``n_features``, ``n_classes``, ``hidden``, ``avg_nnz``, ``avg_labels``,
``nnz_sigma`` and ``dtype``. The functions and names below are the family
interface that ``chipbench/harness.py`` lists; the last three form the
model's half of the plain reference (``chipbench/reference.py`` holds the
rest) and import nothing of the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import synth, work

# ----------------------------------------------------------------------------
# data, provider and model: the program's side
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class Data:
    train: dict
    test: dict
    k: int
    n_lab: int
    stats: dict


def make_data(config: dict, traffic: dict, seeds: dict) -> Data:
    csr = synth.make_xml_csr(
        traffic["samples"], config["n_features"], config["n_classes"],
        config["avg_nnz"], config["avg_labels"], config["nnz_sigma"],
        seeds["data"],
    )
    train, test = synth.split(csr, traffic["test_frac"], seeds["split"])
    k, n_lab = synth.slot_widths(train)
    return Data(train, test, k, n_lab, synth.stats(csr, k))


def make_provider(data: Data, config: dict, traffic: dict, seed: int):
    """The program's SparseProvider over the benchmark's data, recording the
    sample ids of every plan grid it packs (grid ``m`` feeds mega-batch
    ``m``), and the test batches the window evaluates."""
    from repro.data.batcher import SparseBatcher
    from repro.data.providers import SparseProvider
    from repro.data.sparse import SparseDataset

    @dataclasses.dataclass
    class RecordingProvider(SparseProvider):
        grids: list = dataclasses.field(default_factory=list)

        def stack_plan(self, grid, b_slots, out=None):
            self.grids.append([[None if p is None else np.array(p.ids)
                                for p in row] for row in grid])
            return super().stack_plan(grid, b_slots, out=out)

    def dataset(c):
        return SparseDataset(
            n_features=config["n_features"], n_classes=config["n_classes"],
            indptr=c["indptr"], indices=c["indices"], values=c["values"],
            label_ptr=c["label_ptr"], labels=c["labels"],
        )

    batcher = SparseBatcher(dataset(data.train), max_nnz=data.k,
                            max_labels=data.n_lab, seed=seed)
    provider = RecordingProvider(batcher)
    test_batches = provider.test_batches(dataset(data.test), traffic["b_max"],
                                         max_samples=traffic["eval_samples"])
    return provider, test_batches


def make_model(config: dict):
    from repro.models.xml_mlp import XMLMLPConfig, make_model

    if config["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {config['dtype']!r}")
    return make_model(XMLMLPConfig(
        n_features=config["n_features"], n_classes=config["n_classes"],
        hidden=config["hidden"],
    ))


# what ``repro.launch.train`` passes the trainer for this model
TRAINER_KWARGS = {"sparse_grads": True}


def half_batch():
    """Fault: each packed batch keeps only the first half of its valid
    samples; the loss is then the mean over the rest."""
    from chipbench.faults import patched
    from repro.data import providers

    def replacement(original):
        def stack_lazy_plan(*args, **kwargs):
            out = original(*args, **kwargs)
            mask = out["sample_mask"]
            keep = (np.arange(mask.shape[-1])
                    < -(-mask.sum(axis=-1, keepdims=True) // 2))
            mask &= keep
            return out
        return stack_lazy_plan

    return patched(providers, "stack_lazy_plan", replacement)


# ----------------------------------------------------------------------------
# work counted from the batches
# ----------------------------------------------------------------------------


def n_params(config: dict) -> int:
    """Parameters of one replica, as the merge reads and writes them."""
    hidden = config["hidden"]
    return (config["n_features"] * hidden + hidden
            + hidden * config["n_classes"] + config["n_classes"])


def train_work(config: dict, data: Data, grids: list, kind: str) -> dict:
    """Least spmm seconds, model FLOPs and samples of the trained grids."""
    hidden, n_classes = config["hidden"], config["n_classes"]
    spmm_least, model_flops, trained = 0.0, 0.0, 0
    csr = data.train
    for grid in grids:
        for row in grid:
            for ids in row:
                if ids is None:
                    continue
                b = _padded(csr, ids, data.k)
                f, by = work.spmm_work(b["feat_idx"], b["feat_mask"],
                                       b["sample_mask"], hidden)
                spmm_least += work.least_seconds(f, by, kind)
                nnz = b["feat_mask"].sum(axis=1)[b["sample_mask"]]
                model_flops += float(work.model_flops_per_sample(
                    nnz, hidden, n_classes).sum())
                trained += len(ids)
    return {"spmm_least_s": spmm_least, "model_flops": model_flops,
            "trained_samples": trained}


def eval_work(config: dict, data: Data, test_batches: list, kind: str) -> dict:
    """Least kernel seconds of one evaluation over ``test_batches`` on one
    chip."""
    eval_least = 0.0
    for batch in test_batches:
        f, by = work.spmm_work(batch.feat_idx, batch.feat_mask,
                               batch.sample_mask, config["hidden"])
        eval_least += work.least_seconds(f, by, kind)
    return {"spmm_least_s": eval_least}


def _padded(csr, ids, k):
    b = _pack_csr(csr, [ids], len(ids), k, 1)
    return {key: v[0] for key, v in b.items()}


# ----------------------------------------------------------------------------
# the model's half of the plain reference
# ----------------------------------------------------------------------------


def init_params(seed: int, config: dict, dtype=jnp.float32) -> dict:
    """w1 ~ N(0, 1/n_features) and w2 ~ N(0, 1/hidden) from the two halves
    of ``split(PRNGKey(seed))``; biases zero (the configuration's ``init``)."""
    n_features, n_classes, hidden = (config["n_features"], config["n_classes"],
                                     config["hidden"])
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w1 = jax.random.normal(k1, (n_features, hidden), jnp.float32)
    w2 = jax.random.normal(k2, (hidden, n_classes), jnp.float32)
    return {
        "w1": (w1 * (1.0 / jnp.sqrt(n_features))).astype(dtype),
        "b1": jnp.zeros((hidden,), dtype),
        "w2": (w2 * (1.0 / jnp.sqrt(hidden))).astype(dtype),
        "b2": jnp.zeros((n_classes,), dtype),
    }


def loss(params: dict, batch: dict):
    """Mean over valid samples of the mean over each sample's labels of
    -log softmax; the input layer is a gather of W1 rows weighted by the
    slot values."""
    dtype = params["w1"].dtype
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    scale = (batch["feat_val"] * batch["feat_mask"]).astype(dtype)
    rows = params["w1"][batch["feat_idx"]]
    h = jax.nn.relu(
        jnp.einsum("bk,bkh->bh", scale, rows, precision=prec) + params["b1"]
    )
    logits = jnp.dot(h, params["w2"], precision=prec) + params["b2"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    lab = jnp.take_along_axis(logp, batch["label_idx"], axis=-1)
    lmask = batch["label_mask"].astype(dtype)
    per_sample = -jnp.sum(lab * lmask, axis=-1) / jnp.maximum(
        jnp.sum(lmask, axis=-1), 1
    )
    smask = batch["sample_mask"].astype(dtype)
    return jnp.sum(per_sample * smask) / jnp.maximum(jnp.sum(smask), 1)


def pack(data: Data, ids_per_replica: list, b_max: int) -> dict:
    """(R, b_max, ...) padded training batches of the recorded sample ids."""
    return _pack_csr(data.train, ids_per_replica, b_max, data.k, data.n_lab)


def _pack_csr(csr: dict, ids_per_replica: list, b_max: int, k: int,
              n_lab: int) -> dict:
    """(R, b_max, ...) padded batches; a replica with ``None`` gets an empty
    batch. A sample keeps its first ``k`` features and ``n_lab`` labels."""
    r = len(ids_per_replica)
    out = {
        "feat_idx": np.zeros((r, b_max, k), np.int32),
        "feat_val": np.zeros((r, b_max, k), np.float32),
        "feat_mask": np.zeros((r, b_max, k), bool),
        "label_idx": np.zeros((r, b_max, n_lab), np.int32),
        "label_mask": np.zeros((r, b_max, n_lab), bool),
        "sample_mask": np.zeros((r, b_max), bool),
    }
    for i, ids in enumerate(ids_per_replica):
        for row, sid in enumerate(() if ids is None else ids):
            s, e = csr["indptr"][sid], csr["indptr"][sid + 1]
            n = min(e - s, k)
            out["feat_idx"][i, row, :n] = csr["indices"][s:s + n]
            out["feat_val"][i, row, :n] = csr["values"][s:s + n]
            out["feat_mask"][i, row, :n] = True
            s, e = csr["label_ptr"][sid], csr["label_ptr"][sid + 1]
            n = min(e - s, n_lab)
            out["label_idx"][i, row, :n] = csr["labels"][s:s + n]
            out["label_mask"][i, row, :n] = True
            out["sample_mask"][i, row] = True
    return out

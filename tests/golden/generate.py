"""Regenerate the algorithm-conformance golden file.

Runs every seed algorithm x both engines x {sparse, dense} gradient paths
on a small deterministic XML workload and records per-mega-batch losses
plus merged-parameter fingerprints (per-leaf mean and L2 norm).

The goldens were first recorded from the pre-refactor trainer (the
five-way ``if algo == ...`` branching), so ``tests/test_algorithms.py``
proves the pluggable-strategy refactor numerically identical to the seed
behavior. They were regenerated once since, for the vectorized synthetic
data generator and the installed JAX's PRNG default (``jax_version`` in the
file records which JAX produced them). Regenerate only when the *intended*
numerics change (and say so in the change):

    PYTHONPATH=src python tests/golden/generate.py

This module is also the **single source of the case definition**: the
conformance suite imports ``DATASET_KW``/``MODEL_CFG``/``CASE_KW``/
``build_case_trainer``/``fingerprint`` from here, so the recorded and the
replayed runs cannot drift apart.

Algorithms added after the refactor (e.g. ``delayed_sync``) are covered by
cross-engine/cross-path differential tests instead of goldens; only the
five seed algorithms are recorded here.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from repro.configs.base import ElasticConfig
from repro.core.trainer import ElasticTrainer
from repro.data.providers import SparseProvider
from repro.data.sparse import train_test_split
from repro.data.xml_synth import make_xml_dataset
from repro.models.xml_mlp import XMLMLPConfig, make_model

SEED_ALGOS = ("adaptive", "elastic", "sync", "crossbow", "single")
ENGINES = ("scan", "legacy_loop")
N_MEGA = 2
OUT = os.path.join(os.path.dirname(__file__), "algorithms_seed.json")

# the deterministic case every golden was recorded with
DATASET_KW = dict(n_samples=1536, n_features=512, n_classes=64, avg_nnz=24,
                  seed=0)
MODEL_CFG = XMLMLPConfig(n_features=512, n_classes=64, hidden=48)
CASE_KW = dict(b_max=32, mega_batch=6, provider_seed=3, base_lr=0.5, seed=3)


def make_case_dataset():
    full = make_xml_dataset(**DATASET_KW)
    return train_test_split(full, 0.15)[0]


def build_case_trainer(algo: str, engine: str, sparse: bool, ds,
                       placement: str = "vmap") -> ElasticTrainer:
    """``placement`` is not part of the recorded goldens (they predate it);
    the conformance suite passes 'sharded' to replay the same case through
    the shard_map executor and compare against the vmap run."""
    from repro.core import algorithms

    R = algorithms.get(algo).resolve_n_replicas(4)
    prov = SparseProvider.make(ds, seed=CASE_KW["provider_seed"])
    cfg = ElasticConfig.from_bmax(
        CASE_KW["b_max"], algorithm=algo, n_replicas=R,
        mega_batch=CASE_KW["mega_batch"], placement=placement,
    )
    return ElasticTrainer(
        make_model(MODEL_CFG), prov, cfg, base_lr=CASE_KW["base_lr"],
        seed=CASE_KW["seed"], engine=engine, sparse_grads=sparse,
    )


def fingerprint(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        arr = np.asarray(leaf, np.float64)
        out[key] = {"mean": float(arr.mean()), "l2": float(np.linalg.norm(arr))}
    return out


def run_case(algo: str, engine: str, sparse: bool) -> dict:
    tr = build_case_trainer(algo, engine, sparse, make_case_dataset())
    state = tr.init_state()
    losses, accs, us = [], [], []
    for _ in range(N_MEGA):
        state, info = tr.run_megabatch(state)
        losses.append(float(info["train_loss"]))
        accs.append(float(info["train_accuracy"]))
        us.append(info["u"])
    merged = state.global_model
    if merged is None:  # algorithms that keep no separate global copy
        merged = jax.tree_util.tree_map(lambda l: l[0], state.replicas)
    return {
        "train_loss": losses,
        "train_accuracy": accs,
        "u": us,
        "b": np.asarray(state.b, np.float64).tolist(),
        "lr": np.asarray(state.lr, np.float64).tolist(),
        "global": fingerprint(merged),
        "replicas": fingerprint(state.replicas),
    }


def main():
    golden = {"n_megabatches": N_MEGA, "jax_version": jax.__version__,
              "cases": {}}
    for algo in SEED_ALGOS:
        for engine in ENGINES:
            for sparse in (True, False):
                key = f"{algo}|{engine}|{'sparse' if sparse else 'dense'}"
                print("running", key)
                golden["cases"][key] = run_case(algo, engine, sparse)
    with open(OUT, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
    print("wrote", OUT, f"({len(golden['cases'])} cases)")


if __name__ == "__main__":
    main()

"""Conformance suite for the pluggable Algorithm API (DESIGN.md §4).

Three layers of guarantees:

1. **Seed-behavior goldens** — every pre-refactor algorithm must reproduce
   the losses and merged-parameter fingerprints recorded from the five-way
   ``if algo == ...`` trainer before the strategy refactor
   (tests/golden/algorithms_seed.json, regenerated only deliberately via
   tests/golden/generate.py), on both engines, sparse and dense paths.
2. **Registry-wide conformance** — every *registered* algorithm (including
   ones added after the goldens, e.g. ``delayed_sync``, and any future
   plugin) must produce identical results on the scan and legacy engines
   and must match its dense-autodiff oracle on the sparse path.
3. **Public-API extensibility** — a toy algorithm registered through
   nothing but ``@algorithms.register`` runs end-to-end, including through
   ``launch/train.py --algorithm``.
"""
from __future__ import annotations

import json
import os
import re

import jax
import numpy as np
import pytest

from repro.core import algorithms

# the case definition (dataset, model, trainer settings, fingerprinting) is
# owned by the golden generator — importing it guarantees the replayed runs
# cannot drift from what the goldens were recorded with
from golden.generate import (
    ENGINES,
    N_MEGA,
    OUT as GOLDEN_PATH,
    build_case_trainer,
    fingerprint as _fingerprint,
    make_case_dataset,
)

with open(GOLDEN_PATH) as f:
    GOLDEN = json.load(f)
assert GOLDEN["n_megabatches"] == N_MEGA, (
    "golden file out of date — regenerate via tests/golden/generate.py"
)

SEED_ALGOS = sorted({k.split("|")[0] for k in GOLDEN["cases"]})

_cache: dict = {}


def _case(algo: str, engine: str, sparse: bool, placement: str = "vmap"):
    """One deterministic training run; cached — each (algo, engine, path)
    combination is executed once and shared by all assertions on it."""
    key = (algo, engine, sparse, placement)
    if key not in _cache:
        if "ds" not in _cache:
            _cache["ds"] = make_case_dataset()
        tr = build_case_trainer(algo, engine, sparse, _cache["ds"],
                                placement=placement)
        state = tr.init_state()
        infos = []
        for _ in range(N_MEGA):
            state, info = tr.run_megabatch(state)
            infos.append(info)
        _cache[key] = (state, infos)
    return _cache[key]


def _assert_tree_close(a, b, **tol):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **tol)


# --------------------------------------------------------------------------
# registry basics
# --------------------------------------------------------------------------


def test_builtin_algorithms_registered():
    avail = algorithms.available()
    for name in (*SEED_ALGOS, "delayed_sync"):
        assert name in avail, f"{name} missing from registry: {avail}"


def test_unknown_algorithm_raises_with_listing():
    with pytest.raises(KeyError, match="registered"):
        algorithms.get("definitely_not_an_algorithm")


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @algorithms.register("adaptive")
        class Impostor(algorithms.Algorithm):
            pass


def test_register_requires_algorithm_subclass():
    with pytest.raises(TypeError):
        algorithms.register("not_a_strategy")(dict)


def test_ci_smoke_matrix_covers_registry():
    """The CI algorithm-smoke matrix must list exactly the built-in
    registry — registering a 7th algorithm without extending the matrix
    (or vice versa) fails here, in tier-1, not in a forgotten YAML."""
    ci = os.path.join(os.path.dirname(__file__), "..", ".github",
                      "workflows", "ci.yml")
    if not os.path.exists(ci):
        pytest.skip("no CI workflow in this checkout")
    with open(ci) as f:
        text = f.read()
    m = re.search(r"algorithm:\s*\n?\s*\[([^\]]+)\]", text)
    assert m, "could not locate the algorithm matrix in ci.yml"
    matrix = {a.strip() for a in m.group(1).replace("\n", " ").split(",")}
    # toy_* strategies are registered by this test module, not shipped
    builtin = {n for n in algorithms.available() if not n.startswith("toy_")}
    assert matrix == builtin, (
        f"CI matrix {sorted(matrix)} != registry {sorted(builtin)}; "
        "update .github/workflows/ci.yml"
    )


# --------------------------------------------------------------------------
# 1. seed-behavior goldens (pre-refactor parity)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense"])
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("algo", SEED_ALGOS)
def test_matches_pre_refactor_golden(algo, engine, sparse):
    want = GOLDEN["cases"][f"{algo}|{engine}|{'sparse' if sparse else 'dense'}"]
    state, infos = _case(algo, engine, sparse)

    np.testing.assert_allclose(
        [i["train_loss"] for i in infos], want["train_loss"],
        rtol=1e-5, atol=1e-7,
    )
    np.testing.assert_allclose(
        [i["train_accuracy"] for i in infos], want["train_accuracy"],
        rtol=1e-5, atol=1e-6,
    )
    assert [i["u"] for i in infos] == want["u"]
    np.testing.assert_allclose(np.asarray(state.b), want["b"], rtol=1e-12)
    np.testing.assert_allclose(np.asarray(state.lr), want["lr"], rtol=1e-12)

    merged = state.global_model
    if merged is None:
        merged = jax.tree_util.tree_map(lambda l: l[0], state.replicas)
    for k, fp in _fingerprint(merged).items():
        np.testing.assert_allclose(fp["mean"], want["global"][k]["mean"],
                                   rtol=1e-5, atol=1e-8, err_msg=f"global/{k}")
        np.testing.assert_allclose(fp["l2"], want["global"][k]["l2"],
                                   rtol=1e-5, err_msg=f"global/{k}")
    for k, fp in _fingerprint(state.replicas).items():
        np.testing.assert_allclose(fp["l2"], want["replicas"][k]["l2"],
                                   rtol=1e-5, err_msg=f"replicas/{k}")


# --------------------------------------------------------------------------
# 2. registry-wide conformance: every registered algorithm, both engines,
#    sparse and dense gradient paths
# --------------------------------------------------------------------------


@pytest.mark.parametrize("algo", algorithms.available())
def test_engine_parity(algo):
    """scan and legacy_loop must agree on losses, update counts and params."""
    st_s, inf_s = _case(algo, "scan", True)
    st_l, inf_l = _case(algo, "legacy_loop", True)
    np.testing.assert_allclose(
        [i["train_loss"] for i in inf_s], [i["train_loss"] for i in inf_l],
        rtol=2e-4, atol=1e-5,
    )
    assert [i["u"] for i in inf_s] == [i["u"] for i in inf_l]
    _assert_tree_close(st_s.replicas, st_l.replicas, rtol=1e-4, atol=1e-5)
    if st_s.global_model is not None and st_l.global_model is not None:
        _assert_tree_close(st_s.global_model, st_l.global_model,
                           rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algo", algorithms.available())
def test_sparse_dense_parity(algo):
    """The row-sparse gradient path must match its dense-autodiff oracle."""
    st_s, inf_s = _case(algo, "scan", True)
    st_d, inf_d = _case(algo, "scan", False)
    np.testing.assert_allclose(
        [i["train_loss"] for i in inf_s], [i["train_loss"] for i in inf_d],
        rtol=2e-4, atol=1e-5,
    )
    _assert_tree_close(st_s.replicas, st_d.replicas, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algo", algorithms.available())
def test_metrics_contract(algo):
    """Every strategy must fill the engine's full metrics-log contract."""
    _, infos = _case(algo, "scan", True)
    rec = infos[-1]
    for key in ("u", "b", "lr", "alphas", "pert_active", "train_loss",
                "train_accuracy", "virtual_time", "n_rounds"):
        assert key in rec, f"{algo} missing {key}"
    R = algorithms.get(algo).resolve_n_replicas(4)
    assert len(rec["u"]) == len(rec["b"]) == len(rec["alphas"]) == R
    assert np.isfinite(rec["train_loss"])


# --------------------------------------------------------------------------
# sharded placement (DESIGN.md §5): the shard_map replica executor must be a
# drop-in for the vmapped one, for every registered algorithm x both engines
# --------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("algo", algorithms.available())
def test_sharded_placement_parity(algo, engine):
    """placement='sharded' must reproduce the vmap path. In-process jax has
    one device, so the replica mesh is size 1: every collective (psum /
    pmean / pmax) degenerates to the identity and the comparison is
    BIT-LEVEL — any reduction routed around the collective helpers, or any
    reordering of the merge math, fails exactly. Real multi-device
    execution (collectives with >1 shard, float reassociation tolerance)
    is covered by tests/test_sharded_placement.py in a subprocess with 8
    virtual devices — the layout the multi-device CI job runs."""
    st_v, inf_v = _case(algo, engine, True, "vmap")
    st_s, inf_s = _case(algo, engine, True, "sharded")
    assert [i["train_loss"] for i in inf_v] == [i["train_loss"] for i in inf_s]
    assert [i["u"] for i in inf_v] == [i["u"] for i in inf_s]
    _assert_tree_close(st_v.replicas, st_s.replicas, rtol=0, atol=0)
    if st_v.global_model is not None:
        _assert_tree_close(st_v.global_model, st_s.global_model,
                           rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(st_v.b), np.asarray(st_s.b),
                               rtol=1e-12)


def test_sharded_placement_rejects_bad_config():
    from repro.core.trainer import ElasticTrainer

    if "ds" not in _cache:
        _cache["ds"] = make_case_dataset()
    tr = build_case_trainer("adaptive", "scan", True, _cache["ds"])
    import dataclasses

    with pytest.raises(ValueError, match="placement"):
        ElasticTrainer(
            tr.model, tr.provider,
            dataclasses.replace(tr.cfg, placement="teleported"),
        )


# --------------------------------------------------------------------------
# delayed_sync (the sixth algorithm) semantics
# --------------------------------------------------------------------------


def test_delayed_sync_mask_weighted_mean():
    """Masked replicas' zero gradients must not dilute the live mean."""
    import jax.numpy as jnp
    from repro.core.algorithms.delayed_sync import masked_mean_grads

    g = {"w": jnp.asarray([[2.0, 4.0], [0.0, 0.0], [4.0, 8.0]])}
    mask = jnp.asarray([1.0, 0.0, 1.0])
    out = masked_mean_grads(g, mask)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.broadcast_to([3.0, 6.0], (3, 2)))


def test_delayed_sync_charges_one_merge_per_megabatch():
    """The delay hides aggregation latency: one barrier cost, not per-round
    like `sync` — that is the algorithm's entire virtual-time advantage."""
    _, inf_ds = _case("delayed_sync", "scan", True)
    _, inf_sy = _case("sync", "scan", True)
    assert inf_ds[-1]["virtual_time"] < inf_sy[-1]["virtual_time"]


def test_delayed_sync_adapts_batch_sizes():
    state, infos = _case("delayed_sync", "scan", True)
    b = np.asarray(state.b)
    assert not np.allclose(b, b[0]) or np.any(b < 32.0), (
        "batch sizes never adapted under heterogeneity"
    )


# --------------------------------------------------------------------------
# 3. extensibility through the public API only
# --------------------------------------------------------------------------


@algorithms.register("toy_halfstep")
class ToyHalfStep(algorithms.Algorithm):
    """Toy plugin: elastic averaging that halves the merge contribution of
    the slowest replica — registered with zero trainer edits."""

    def merge(self, trainer, state, plan, replicas):
        import numpy as _np

        alphas = _np.ones(trainer.cfg.n_replicas)
        alphas[int(_np.argmin(plan.u))] *= 0.5
        alphas /= alphas.sum()
        new_global, new_replicas = trainer.merge_models(
            replicas, alphas, None, None, 0.0
        )
        return algorithms.MergeOutcome(
            replicas=new_replicas, global_model=new_global, alphas=alphas
        )


def test_toy_algorithm_via_public_api():
    """The registered toy strategy trains end-to-end on both engines and
    its merge weights reach the metrics log."""
    st_s, inf_s = _case("toy_halfstep", "scan", True)
    st_l, inf_l = _case("toy_halfstep", "legacy_loop", True)
    assert np.isfinite(inf_s[-1]["train_loss"])
    np.testing.assert_allclose(
        [i["train_loss"] for i in inf_s], [i["train_loss"] for i in inf_l],
        rtol=2e-4, atol=1e-5,
    )
    assert abs(sum(inf_s[-1]["alphas"]) - 1.0) < 1e-6
    assert min(inf_s[-1]["alphas"]) < 1.0 / 4


def test_toy_algorithm_through_launcher():
    """--algorithm picks up registry plugins with no launcher edits."""
    from repro.launch import train as train_mod

    state, mlog, _ = train_mod.main([
        "--workload", "xml", "--algorithm", "toy_halfstep", "--replicas", "2",
        "--megabatches", "1", "--mega-batch", "2", "--b-max", "16",
        "--samples", "256", "--features", "128", "--classes", "32",
        "--avg-nnz", "8", "--hidden", "16", "--lr", "0.5",
    ])
    assert len(mlog.records) == 1
    assert np.isfinite(mlog.records[-1]["train_loss"])

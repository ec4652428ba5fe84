"""Sparse-gradient path tests (DESIGN.md §3).

Three layers of differential coverage:

* kernel: ``jax.grad`` through the ``ops.spmm`` custom VJP (Pallas forward +
  sorted scatter-add backward, interpret mode on CPU) vs ``jax.grad``
  through the pure-jnp ``_sparse_input_ref`` gather — swept over shapes x
  dtypes x block_k, with duplicate indices inside one sample and
  fully-masked samples;
* model: ``loss_and_sparse_grad`` (row-sparse d w1, no autodiff over the
  input layer) vs dense ``jax.value_and_grad(loss_fn)``;
* trainer: sparse path vs dense oracle for all 5 algorithms under both
  engines, and masked (bucket-padding) rounds stay exact no-ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ElasticConfig
from repro.core.trainer import ElasticTrainer
from repro.data.providers import SparseProvider
from repro.data.sparse import train_test_split
from repro.data.xml_synth import make_xml_dataset
from repro.kernels.spmm.ops import spmm, spmm_grad_w
from repro.kernels.spmm.ref import spmm_grad_w_ref
from repro.models.xml_mlp import (
    XMLMLPConfig,
    loss_and_sparse_grad,
    loss_fn,
    make_model,
)
from repro.optim.row_sparse import RowSparseGrad, is_row_sparse
from repro.optim.sgd import SGDConfig

RNG = np.random.default_rng(7)
ALGOS = ["adaptive", "elastic", "sync", "crossbow", "single"]


def _f32(x):
    return np.asarray(x, np.float32)


def _tol(dtype):
    # bf16 grads are quantized on both sides with different summation
    # orders: allow a couple of ulp at the observed magnitudes
    return dict(rtol=5e-2, atol=1.5e-1) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-5
    )


def _batch(b, k, nf, duplicate=False, mask_sample=None, rng=None):
    rng = rng if rng is not None else np.random.default_rng(b * 1000 + k)
    fi = rng.integers(0, nf, (b, k)).astype(np.int32)
    if duplicate and k >= 2:  # same row twice in one sample
        fi[0, 1] = fi[0, 0]
    fv = rng.normal(size=(b, k)).astype(np.float32)
    fm = rng.random((b, k)) > 0.3
    if mask_sample is not None:
        fm[mask_sample] = False
    return jnp.asarray(fi), jnp.asarray(fv), jnp.asarray(fm)


# --------------------------------------------------------------------------
# kernel-level: custom VJP vs autodiff of the gather reference
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,k,nf,h", [(4, 16, 512, 128), (8, 7, 300, 512), (2, 33, 1024, 200)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_k", [1, 8])
def test_grad_equivalence_sweep(b, k, nf, h, dtype, block_k):
    rng = np.random.default_rng(nf + h + block_k)
    fi, fv, fm = _batch(b, k, nf, duplicate=True, mask_sample=min(1, b - 1),
                        rng=rng)
    w = jnp.asarray(rng.normal(size=(nf, h)), dtype)
    co = jnp.asarray(rng.normal(size=(b, h)), jnp.float32)

    def f_kernel(v, w):
        return jnp.sum(spmm(fi, v, fm, w, block_k=block_k).astype(jnp.float32) * co)

    def f_ref(v, w):
        rows = w[fi].astype(jnp.float32)
        scale = (v * fm).astype(jnp.float32)[..., None]
        return jnp.sum(jnp.sum(rows * scale, axis=1) * co)

    gv_k, gw_k = jax.grad(f_kernel, (0, 1))(fv, w)
    gv_r, gw_r = jax.grad(f_ref, (0, 1))(fv, w)
    np.testing.assert_allclose(_f32(gw_k), _f32(gw_r), **_tol(dtype))
    np.testing.assert_allclose(_f32(gv_k), _f32(gv_r), **_tol(dtype))


@pytest.mark.parametrize("chunk", [8, 128, 512])
def test_grad_w_standalone_vs_ref(chunk):
    b, k, nf, h = 4, 9, 200, 160
    fi, fv, fm = _batch(b, k, nf, duplicate=True)
    dh = jnp.asarray(RNG.normal(size=(b, h)), jnp.float32)
    got = spmm_grad_w(fi, fv, fm, dh, nf, chunk=chunk)
    want = spmm_grad_w_ref(fi, fv, fm, dh, nf)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-5, atol=1e-5)


def test_grad_w_all_masked_is_zero():
    b, k, nf, h = 3, 5, 64, 128
    fi = jnp.zeros((b, k), jnp.int32)
    fv = jnp.ones((b, k), jnp.float32)
    fm = jnp.zeros((b, k), bool)
    dh = jnp.asarray(RNG.normal(size=(b, h)), jnp.float32)
    np.testing.assert_allclose(_f32(spmm_grad_w(fi, fv, fm, dh, nf)), 0.0)


def test_grad_heavily_duplicated_rows():
    """All nnz of all samples hit the same two rows — the worst write-conflict
    case the sorted formulation must serialize correctly."""
    b, k, nf, h = 4, 12, 50, 256
    fi = jnp.asarray(RNG.integers(0, 2, (b, k)), jnp.int32)
    fv = jnp.asarray(RNG.normal(size=(b, k)), jnp.float32)
    fm = jnp.ones((b, k), bool)
    dh = jnp.asarray(RNG.normal(size=(b, h)), jnp.float32)
    got = spmm_grad_w(fi, fv, fm, dh, nf)
    want = spmm_grad_w_ref(fi, fv, fm, dh, nf)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-4, atol=1e-4)
    assert np.all(_f32(got)[2:] == 0.0)  # untouched rows stay zero


# --------------------------------------------------------------------------
# model-level: row-sparse grads vs dense autodiff
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def xml_data():
    full = make_xml_dataset(
        n_samples=1024, n_features=512, n_classes=64, avg_nnz=24, seed=0
    )
    return train_test_split(full, 0.15)


def _model_batch(xml_data, b_slots=16, seed=0):
    ds, _ = xml_data
    prov = SparseProvider.make(ds, seed=seed)
    payload = prov.fetch(b_slots - 2, b_slots)  # 2 masked samples
    return {k: jnp.asarray(v) for k, v in prov.stack([payload]).items()}


def test_sparse_grad_matches_dense_autodiff(xml_data):
    cfg = XMLMLPConfig(n_features=512, n_classes=64, hidden=48)
    params = make_model(cfg)["init"](jax.random.PRNGKey(0))
    batch = {k: v[0] for k, v in _model_batch(xml_data).items()}

    (loss_s, aux_s), grads = loss_and_sparse_grad(cfg, params, batch)
    (loss_d, aux_d), dense = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch), has_aux=True
    )(params)

    np.testing.assert_allclose(float(loss_s), float(loss_d), rtol=1e-6)
    np.testing.assert_allclose(float(aux_s["n_valid"]), float(aux_d["n_valid"]))
    assert is_row_sparse(grads["w1"])
    np.testing.assert_allclose(
        _f32(grads["w1"].densify()), _f32(dense["w1"]), rtol=1e-5, atol=1e-6
    )
    for k in ("b1", "w2", "b2"):
        np.testing.assert_allclose(_f32(grads[k]), _f32(dense[k]),
                                   rtol=1e-5, atol=1e-6)


def test_kernel_routed_model_grads_match_ref(xml_data):
    """use_spmm_kernel=True (forced; interpret mode on CPU) runs the whole
    loss through the Pallas forward + custom VJP and must match the jnp
    input layer, dense grads and sparse grads alike."""
    cfg_k = XMLMLPConfig(n_features=512, n_classes=64, hidden=48,
                         use_spmm_kernel=True)
    cfg_r = XMLMLPConfig(n_features=512, n_classes=64, hidden=48,
                         use_spmm_kernel=False)
    params = make_model(cfg_r)["init"](jax.random.PRNGKey(1))
    batch = {k: v[0] for k, v in _model_batch(xml_data, b_slots=8).items()}

    (l_k, _), g_k = jax.value_and_grad(
        lambda p: loss_fn(cfg_k, p, batch), has_aux=True
    )(params)
    (l_r, _), g_r = jax.value_and_grad(
        lambda p: loss_fn(cfg_r, p, batch), has_aux=True
    )(params)
    np.testing.assert_allclose(float(l_k), float(l_r), rtol=1e-5)
    for k in g_r:
        np.testing.assert_allclose(_f32(g_k[k]), _f32(g_r[k]),
                                   rtol=1e-4, atol=1e-5)

    (_, _), gs = loss_and_sparse_grad(cfg_k, params, batch)
    np.testing.assert_allclose(
        _f32(gs["w1"].densify()), _f32(g_r["w1"]), rtol=1e-4, atol=1e-5
    )


def test_sparse_grad_vmaps_over_replicas(xml_data):
    """RowSparseGrad must survive vmap (static shapes, registered pytree)."""
    cfg = XMLMLPConfig(n_features=512, n_classes=64, hidden=48)
    params = make_model(cfg)["init"](jax.random.PRNGKey(0))
    import repro.utils.tree as tu

    R = 3
    reps = tu.tree_broadcast_replicas(params, R)
    batch = _model_batch(xml_data)
    batch = {k: jnp.broadcast_to(v[0][None], (R,) + v[0].shape) for k, v in batch.items()}
    (loss, _), grads = jax.vmap(
        lambda p, b: loss_and_sparse_grad(cfg, p, b)
    )(reps, batch)
    assert loss.shape == (R,)
    assert grads["w1"].rows.shape[0] == R
    assert grads["w1"].vals.shape[0] == R
    d = grads["w1"].densify()
    assert d.shape == (R, 512, 48)
    np.testing.assert_allclose(_f32(d[0]), _f32(d[1]), rtol=1e-6)


# --------------------------------------------------------------------------
# trainer-level: sparse path vs dense oracle, both engines, all algorithms
# --------------------------------------------------------------------------


def _run(algo, xml_data, engine, sparse, n_mega=2, seed=3, bucket=True):
    ds, _ = xml_data
    R = 1 if algo == "single" else 4
    prov = SparseProvider.make(ds, seed=seed)
    cfg = ElasticConfig.from_bmax(32, algorithm=algo, n_replicas=R, mega_batch=5)
    tr = ElasticTrainer(
        make_model(XMLMLPConfig(n_features=512, n_classes=64, hidden=48)),
        prov, cfg, base_lr=0.5, seed=seed, engine=engine,
        sparse_grads=sparse,
    )
    tr.round_bucket = bucket
    state = tr.init_state()
    infos = []
    for _ in range(n_mega):
        state, info = tr.run_megabatch(state)
        infos.append(info)
    return state, infos


def _assert_tree_close(a, b, **tol):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **tol)


@pytest.mark.parametrize("engine", ["scan", "legacy_loop"])
@pytest.mark.parametrize("algo", ALGOS)
def test_sparse_matches_dense_oracle(algo, engine, xml_data):
    st_s, inf_s = _run(algo, xml_data, engine, sparse=True)
    st_d, inf_d = _run(algo, xml_data, engine, sparse=False)
    np.testing.assert_allclose(
        [i["train_loss"] for i in inf_s],
        [i["train_loss"] for i in inf_d],
        rtol=2e-4, atol=1e-5,
    )
    _assert_tree_close(st_s.replicas, st_d.replicas, rtol=1e-4, atol=1e-5)
    if st_s.global_model is not None:
        _assert_tree_close(st_s.global_model, st_d.global_model,
                           rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algo", ALGOS)
def test_masked_round_noop_scan_engine(algo, xml_data):
    """Bucket-padding (fully-masked) rounds must be exact no-ops on the
    sparse path under the scan engine, for every algorithm."""
    st_pad, inf_pad = _run(algo, xml_data, "scan", sparse=True, n_mega=1,
                           bucket=True)
    st_raw, inf_raw = _run(algo, xml_data, "scan", sparse=True, n_mega=1,
                           bucket=False)
    np.testing.assert_allclose(
        inf_pad[0]["train_loss"], inf_raw[0]["train_loss"], rtol=1e-5, atol=1e-6
    )
    _assert_tree_close(st_pad.replicas, st_raw.replicas, rtol=1e-5, atol=1e-6)


def test_sparse_update_mask_freezes_replica_rows(xml_data):
    """A zero update-mask entry must freeze the replica's w1 exactly, even
    though the scatter touches its rows."""
    from repro.optim.sgd import sgd_update

    NF, H, S, R = 40, 6, 10, 2
    p = {"w1": jnp.asarray(RNG.normal(size=(R, NF, H)), jnp.float32)}
    rows = jnp.asarray(RNG.integers(0, NF, (R, S)), jnp.int32)
    vals = jnp.asarray(RNG.normal(size=(R, S, H)), jnp.float32)
    g = {"w1": RowSparseGrad(rows, vals, NF)}
    mask = jnp.asarray([0.0, 1.0])
    new, _ = sgd_update(p, g, 0.5, SGDConfig(), update_mask=mask,
                        replica_dim=True)
    np.testing.assert_array_equal(np.asarray(new["w1"][0]),
                                  np.asarray(p["w1"][0]))
    assert not np.array_equal(np.asarray(new["w1"][1]), np.asarray(p["w1"][1]))

"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp ref.py oracle, plus hypothesis property tests on kernel invariants.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.moe_gmm.ops import moe_ffn_gmm
from repro.kernels.moe_gmm.ref import moe_ffn_gmm_ref
from repro.kernels.spmm.ops import spmm
from repro.kernels.spmm.ref import spmm_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.weighted_merge.ops import merge, merge_pytree
from repro.kernels.weighted_merge.ref import weighted_merge_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-5
    )


def _f32(x):
    return np.asarray(x, np.float32)


# --------------------------------------------------------------------------
# weighted_merge
# --------------------------------------------------------------------------


@pytest.mark.parametrize("r,n", [(2, 256), (4, 2048), (8, 5001), (3, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weighted_merge_sweep(r, n, dtype):
    reps = jnp.asarray(RNG.normal(size=(r, n)), dtype)
    alphas = jnp.asarray(RNG.random(r), jnp.float32)
    got = merge(reps, alphas)
    want = weighted_merge_ref(reps, alphas)
    np.testing.assert_allclose(_f32(got), _f32(want), **_tol(dtype))


@pytest.mark.parametrize("r,n", [(4, 1000), (2, 4096)])
def test_weighted_merge_momentum(r, n):
    reps = jnp.asarray(RNG.normal(size=(r, n)), jnp.float32)
    alphas = jnp.asarray(RNG.random(r), jnp.float32)
    g = jnp.asarray(RNG.normal(size=n), jnp.float32)
    gp = jnp.asarray(RNG.normal(size=n), jnp.float32)
    got = merge(reps, alphas, g, gp, 0.9)
    want = weighted_merge_ref(reps, alphas, g, gp, 0.9)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-5, atol=1e-5)


def test_weighted_merge_pytree():
    tree = {
        "a": jnp.asarray(RNG.normal(size=(4, 16, 8)), jnp.float32),
        "b": {"c": jnp.asarray(RNG.normal(size=(4, 100)), jnp.float32)},
    }
    alphas = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    out = merge_pytree(tree, alphas)
    want_a = weighted_merge_ref(tree["a"].reshape(4, -1), alphas).reshape(16, 8)
    np.testing.assert_allclose(_f32(out["a"]), _f32(want_a), rtol=1e-5, atol=1e-6)
    assert out["b"]["c"].shape == (100,)


# Leaves as a TPU v5e stores them: the major-to-minor order of the device's
# default layout for each replica-stacked shape (float32, bfloat16).
MERGE_LEAVES = {
    (4, 128, 1000): ((0, 2, 1), (0, 2, 1)),   # cols not a multiple of 128
    (4, 13, 300): ((1, 0, 2), (1, 0, 2)),     # rows not a multiple of 8
    (1, 128, 2500): ((2, 0, 1), (0, 2, 1)),   # R = 1, as on four chips
    (4, 1001): ((0, 1), (0, 1)),              # a bias
    (2, 3, 16, 200): ((0, 1, 2, 3), (0, 1, 2, 3)),  # an extra leading dim
}
MERGE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _stored_as(layout, dtype):
    """Dimension orders of the test's leaves as the CPU (row-major) or a
    v5e stores them, by shape; a merged leaf as its replicas leave it."""
    from repro.kernels.weighted_merge.weighted_merge import merged_order

    orders = {}
    for shape, by_dtype in MERGE_LEAVES.items():
        order = (tuple(range(len(shape))) if layout == "row_major"
                 else by_dtype[dtype == jnp.bfloat16])
        orders[shape] = order
        orders[shape[1:]] = merged_order(order)
    return orders


def _use_orders(monkeypatch, orders):
    from repro.kernels.weighted_merge import ops

    monkeypatch.setattr(ops, "stored_order",
                        lambda shape, _dtype: orders[tuple(shape)])


def _merge_case(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    reps = jnp.asarray(rng.normal(size=shape), dtype)
    alphas = jnp.asarray(rng.random(shape[0]), jnp.float32)
    g = jnp.asarray(rng.normal(size=shape[1:]), dtype)
    gp = jnp.asarray(rng.normal(size=shape[1:]), dtype)
    return reps, alphas, g, gp


@pytest.mark.parametrize("layout", ["row_major", "v5e"])
@pytest.mark.parametrize("momentum", [False, True], ids=["plain", "momentum"])
@pytest.mark.parametrize("dtype", list(MERGE_DTYPES), ids=str)
@pytest.mark.parametrize("shape", list(MERGE_LEAVES), ids=str)
def test_weighted_merge_stored_layout(monkeypatch, shape, dtype, momentum,
                                      layout):
    """Each leaf merged in the layout it is stored in equals the oracle on
    the flattened view, in the leaf's own shape."""
    dtype = MERGE_DTYPES[dtype]
    _use_orders(monkeypatch, _stored_as(layout, dtype))
    reps, alphas, g, gp = _merge_case(shape, dtype)
    gamma = 0.9 if momentum else 0.0
    got = merge_pytree({"w": reps}, alphas, {"w": g}, {"w": gp}, gamma)["w"]
    want = weighted_merge_ref(
        reps.reshape(shape[0], -1), alphas, g.reshape(-1), gp.reshape(-1),
        gamma,
    ).reshape(shape[1:])
    assert got.shape == shape[1:] and got.dtype == dtype
    tol = _tol(dtype) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(_f32(got), _f32(want), **tol)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("layout", ["row_major", "v5e"])
@pytest.mark.parametrize("shape", list(MERGE_LEAVES), ids=str)
def test_weighted_merge_makes_no_copies(monkeypatch, shape, layout):
    """The merge's program holds no pad, and every reshape keeps its
    operand's last two dimensions; a transpose only views a leaf in the
    order it is stored in (a bitcast on the chip)."""
    stored = _stored_as(layout, jnp.float32)
    _use_orders(monkeypatch, stored)
    reps, alphas, g, gp = _merge_case(shape, jnp.float32)
    for gamma in (0.0, 0.9):
        jaxpr = jax.make_jaxpr(
            lambda x, a, g, gp: merge_pytree({"w": x}, a, {"w": g}, {"w": gp},
                                             gamma)
        )(reps, alphas, g, gp)
        prims = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
        assert "pallas_call" in prims
        assert "pad" not in prims
        for e in _eqns(jaxpr.jaxpr):
            src, dst = e.invars[0].aval.shape, e.outvars[0].aval.shape
            if e.primitive.name == "reshape":
                assert src[-2:] == dst[-2:], (src, dst)
            if e.primitive.name == "transpose":
                perm = tuple(e.params["permutation"])
                to_stored = stored.get(tuple(src)) == perm
                to_logical = (tuple(dst) in stored
                              and tuple(np.argsort(stored[dst])) == perm)
                assert to_stored or to_logical, (src, perm)


@pytest.mark.parametrize("momentum", [False, True], ids=["plain", "momentum"])
def test_weighted_merge_flattens_replicas_on_lanes(monkeypatch, momentum):
    """A leaf stored with its replica axis on the lanes (a v5e keeps (4, 1)
    as (1, 4)), per-replica scalars and a bfloat16 vector a replica are
    flattened first and counted; a float32 vector a replica is not."""
    from repro.kernels.weighted_merge import ops

    orders = {(4, 1): (1, 0), (1,): (0,), (4,): (0,), (): (),
              (4, 1001): (0, 1), (1001,): (0,)}
    _use_orders(monkeypatch, orders)
    tree = {"col": _merge_case((4, 1), jnp.float32)[0],
            "scalar": _merge_case((4,), jnp.float32, seed=1)[0],
            "bias16": _merge_case((4, 1001), jnp.bfloat16, seed=2)[0],
            "bias32": _merge_case((4, 1001), jnp.float32, seed=3)[0]}
    alphas = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    g = {k: jnp.ones(x.shape[1:], x.dtype) for k, x in tree.items()}
    gp = {k: jnp.zeros(x.shape[1:], x.dtype) for k, x in tree.items()}
    gamma = 0.9 if momentum else 0.0
    out = merge_pytree(tree, alphas, g, gp, gamma)
    for k, x in tree.items():
        want = weighted_merge_ref(x.reshape(4, -1), alphas,
                                  g[k].reshape(-1), gp[k].reshape(-1), gamma)
        assert out[k].shape == x.shape[1:] and out[k].dtype == x.dtype
        tol = _tol(x.dtype) if x.dtype == jnp.bfloat16 else dict(rtol=1e-5,
                                                                  atol=1e-6)
        np.testing.assert_allclose(_f32(out[k]).reshape(-1), _f32(want), **tol)
    assert ops.flat_leaves(tree) == 3


@settings(max_examples=20, deadline=None)
@given(
    r=st.integers(2, 8),
    n=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_weighted_merge_property_convex(r, n, seed):
    """Merged model with normalized weights lies in the convex hull: for
    constant replicas the merge returns the constant exactly."""
    rng = np.random.default_rng(seed)
    alphas = rng.random(r).astype(np.float32)
    alphas = alphas / alphas.sum()
    const = rng.normal()
    reps = jnp.full((r, n), const, jnp.float32)
    out = merge(reps, jnp.asarray(alphas))
    np.testing.assert_allclose(_f32(out), const, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# spmm
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,k,nf,h", [(4, 16, 512, 128), (8, 7, 300, 512), (2, 33, 1024, 200)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_k", [1, 8])
def test_spmm_sweep(b, k, nf, h, dtype, block_k):
    fi = jnp.asarray(RNG.integers(0, nf, (b, k)), jnp.int32)
    fv = jnp.asarray(RNG.normal(size=(b, k)), jnp.float32)
    fm = jnp.asarray(RNG.random((b, k)) > 0.3)
    w = jnp.asarray(RNG.normal(size=(nf, h)), dtype)
    got = spmm(fi, fv, fm, w, block_k=block_k)
    want = spmm_ref(fi, fv, fm, w)
    np.testing.assert_allclose(_f32(got), _f32(want), **_tol(dtype))


def test_spmm_all_masked():
    fi = jnp.zeros((2, 4), jnp.int32)
    fv = jnp.ones((2, 4), jnp.float32)
    fm = jnp.zeros((2, 4), bool)
    w = jnp.asarray(RNG.normal(size=(16, 128)), jnp.float32)
    np.testing.assert_allclose(_f32(spmm(fi, fv, fm, w)), 0.0)


@pytest.mark.parametrize("op", ["spmm", "spmm_grad_w"])
def test_spmm_parity_at_amazon_widths(op):
    """The DMA gather and the sorted scatter against ref.py at Amazon-670K's
    input width (NF=135,909, H=128): row ids span the whole table, and rows
    repeat inside a sample and across samples."""
    from repro.data.xml_synth import AMAZON_670K
    from repro.kernels.spmm.ops import spmm_grad_w
    from repro.kernels.spmm.ref import spmm_grad_w_ref

    nf, h, b, k = AMAZON_670K["n_features"], 128, 3, 20
    rng = np.random.default_rng(670)
    fi = rng.integers(0, nf, (b, k)).astype(np.int32)
    fi[:, -1] = nf - 1                 # the table's last row, every sample
    fi[0, 1] = fi[0, 0]                # a row twice in one sample
    fi[2, :3] = fi[1, :3]              # rows shared across samples
    fv = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    fm = jnp.asarray(rng.random((b, k)) > 0.2)
    fi = jnp.asarray(fi)
    if op == "spmm":
        w = jnp.asarray(rng.normal(size=(nf, h)), jnp.float32)
        got, want = spmm(fi, fv, fm, w), spmm_ref(fi, fv, fm, w)
    else:
        dh = jnp.asarray(rng.normal(size=(b, h)), jnp.float32)
        got = spmm_grad_w(fi, fv, fm, dh, nf, chunk=16)
        want = spmm_grad_w_ref(fi, fv, fm, dh, nf)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 4),
    k=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
def test_spmm_property_linearity(b, k, seed):
    """spmm is linear in the values: spmm(2v) == 2 spmm(v)."""
    rng = np.random.default_rng(seed)
    nf, h = 64, 128
    fi = jnp.asarray(rng.integers(0, nf, (b, k)), jnp.int32)
    fv = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    fm = jnp.asarray(rng.random((b, k)) > 0.2)
    w = jnp.asarray(rng.normal(size=(nf, h)), jnp.float32)
    one = spmm(fi, fv, fm, w)
    two = spmm(fi, 2.0 * fv, fm, w)
    np.testing.assert_allclose(_f32(two), 2.0 * _f32(one), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# flash_attention
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,sq,skv,hq,hkv,hd,causal,window",
    [
        (2, 128, 128, 4, 2, 64, True, 0),     # GQA causal
        (1, 256, 256, 8, 2, 32, True, 64),    # sliding window
        (2, 96, 160, 4, 4, 64, False, 0),     # cross (non-causal, Sq != Skv)
        (1, 200, 200, 2, 1, 64, True, 0),     # non-divisible (padding)
    ],
)
def test_flash_attention_sweep(b, sq, skv, hq, hkv, hd, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, sq, hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    b, s, hq, hkv, hd = 1, 128, 4, 2, 64
    q = jnp.asarray(RNG.normal(size=(b, s, hq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, hd)), dtype)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = attention_ref(q, k, v)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=3e-2, atol=3e-2)


def test_flash_attention_matches_model_blockwise():
    """Kernel agrees with the model's jnp online-softmax fallback."""
    from repro.models.layers import blockwise_attention

    b, s, hq, hkv, hd = 2, 128, 8, 4, 32
    q = jnp.asarray(RNG.normal(size=(b, s, hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, hd)), jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_k=64)
    want = blockwise_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), shift=st.floats(-3.0, 3.0))
def test_flash_attention_property_shift_invariance(seed, shift):
    """Softmax shift invariance: adding a constant to all K projections of a
    single position's scores doesn't change output when added uniformly —
    here we test scale stability: outputs are convex combos of V rows, so
    max|out| <= max|V|."""
    rng = np.random.default_rng(seed)
    b, s, h, hd = 1, 64, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)) + shift, jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    assert np.max(np.abs(_f32(out))) <= np.max(np.abs(_f32(v))) + 1e-4


# --------------------------------------------------------------------------
# moe_gmm
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "e,c,d,f", [(4, 64, 128, 256), (2, 100, 64, 300), (8, 32, 256, 512)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_sweep(e, c, d, f, dtype):
    buf = jnp.asarray(RNG.normal(size=(e, c, d)) * 0.5, dtype)
    wi = jnp.asarray(RNG.normal(size=(e, d, f)) * d ** -0.5, dtype)
    wg = jnp.asarray(RNG.normal(size=(e, d, f)) * d ** -0.5, dtype)
    wo = jnp.asarray(RNG.normal(size=(e, f, d)) * f ** -0.5, dtype)
    got = moe_ffn_gmm(buf, wi, wg, wo, block_c=32, block_f=128)
    want = moe_ffn_gmm_ref(buf, wi, wg, wo)
    np.testing.assert_allclose(_f32(got), _f32(want), **_tol(dtype))


def test_moe_gmm_zero_rows_give_zero():
    """Capacity-padding rows (zero inputs) must produce zero outputs."""
    e, c, d, f = 2, 16, 32, 64
    buf = jnp.zeros((e, c, d), jnp.float32)
    wi = jnp.asarray(RNG.normal(size=(e, d, f)), jnp.float32)
    wg = jnp.asarray(RNG.normal(size=(e, d, f)), jnp.float32)
    wo = jnp.asarray(RNG.normal(size=(e, f, d)), jnp.float32)
    np.testing.assert_allclose(
        _f32(moe_ffn_gmm(buf, wi, wg, wo, block_c=16, block_f=32)), 0.0
    )


def test_moe_gmm_matches_moe_layer_path():
    """moe_ffn(use_gmm_kernel=True) == moe_ffn(False) end to end."""
    from repro.models import moe as MOE

    key = jax.random.PRNGKey(0)
    params = MOE.init_moe(key, 64, 128, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    y0, a0 = MOE.moe_ffn(params, x, top_k=2, use_gmm_kernel=False)
    y1, a1 = MOE.moe_ffn(params, x, top_k=2, use_gmm_kernel=True)
    np.testing.assert_allclose(_f32(y0), _f32(y1), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(a0), float(a1), rtol=1e-6)


# --------------------------------------------------------------------------
# ssd_scan
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,l,h,p,n,c",
    [(2, 128, 4, 32, 16, 32), (1, 256, 2, 64, 64, 64), (2, 64, 8, 16, 8, 16)],
)
def test_ssd_scan_sweep(b, l, h, p, n, c):
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)) * 0.5, jnp.float32)
    dA = -jnp.asarray(RNG.random((b, l, h)) * 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    y, fin = ssd_scan(x, dA, Bm, Cm, chunk=c)
    yr, finr = ssd_scan_ref(x, dA, Bm, Cm, c)
    np.testing.assert_allclose(_f32(y), _f32(yr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(_f32(fin), _f32(finr), rtol=1e-4, atol=1e-4)


def test_ssd_scan_bf16_inputs():
    b, l, h, p, n, c = 1, 64, 2, 32, 16, 32
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)) * 0.5, jnp.bfloat16)
    dA = -jnp.asarray(RNG.random((b, l, h)) * 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.bfloat16)
    Cm = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.bfloat16)
    y, _ = ssd_scan(x, dA, Bm, Cm, chunk=c)
    yr, _ = ssd_scan_ref(
        x.astype(jnp.float32), dA, Bm.astype(jnp.float32),
        Cm.astype(jnp.float32), c,
    )
    np.testing.assert_allclose(_f32(y), _f32(yr), rtol=3e-2, atol=3e-2)


def test_ssd_scan_chunk_invariance():
    """Different chunk sizes must give identical results (associativity of
    the inter-chunk recurrence)."""
    b, l, h, p, n = 1, 128, 2, 16, 8
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)) * 0.5, jnp.float32)
    dA = -jnp.asarray(RNG.random((b, l, h)) * 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    y32, f32_ = ssd_scan(x, dA, Bm, Cm, chunk=32)
    y64, f64_ = ssd_scan(x, dA, Bm, Cm, chunk=64)
    np.testing.assert_allclose(_f32(y32), _f32(y64), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(_f32(f32_), _f32(f64_), rtol=1e-4, atol=1e-4)


def test_ssd_scan_matches_recurrent_decode():
    """Kernel output position t == sequential recurrence through t (the
    train/decode consistency invariant that makes the KV-cache-free SSM
    serving path valid)."""
    b, l, h, p, n, c = 1, 32, 2, 8, 4, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(b, l, h, p)) * 0.5, jnp.float32)
    dA = -jnp.asarray(rng.random((b, l, h)) * 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    y, _ = ssd_scan(x, dA, Bm, Cm, chunk=c)
    # naive recurrence: s_t = exp(dA_t) s_{t-1} + B_t x_t^T ; y_t = C_t s_t
    state = np.zeros((b, h, p, n), np.float32)
    for t in range(l):
        da = np.exp(np.asarray(dA[:, t]))  # (b,h)
        bx = np.einsum("bhp,bhn->bhpn", np.asarray(x[:, t]), np.asarray(Bm[:, t]))
        state = state * da[..., None, None] + bx
        yt = np.einsum("bhpn,bhn->bhp", state, np.asarray(Cm[:, t]))
        np.testing.assert_allclose(_f32(y[:, t]), yt, rtol=1e-3, atol=1e-3)

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

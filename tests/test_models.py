"""Model-internals correctness: SSD vs naive recurrence, decode==forward,
blockwise attention vs dense reference, MoE dispatch invariants, the XML
model's stored w1 rows."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS
from repro.models import model as MDL
from repro.models.layers import blockwise_attention
from repro.models.mamba2 import ssd_chunked
from repro.models.moe import _dispatch_indices, moe_ffn, init_moe


def dense_attention_ref(q, k, v, causal=True, window=0):
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    rel = np.arange(sq)[:, None] - np.arange(skv)[None, :]
    allow = np.ones((sq, skv), bool)
    if causal:
        allow &= rel >= 0
    if window:
        allow &= rel < window
    s = np.where(allow[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


class TestBlockwiseAttention:
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 16), (False, 0)])
    @pytest.mark.parametrize("chunk", [8, 32, 64])
    def test_matches_dense(self, causal, window, chunk):
        rng = np.random.default_rng(0)
        q = rng.normal(size=(2, 64, 3, 16)).astype(np.float32)
        k = rng.normal(size=(2, 64, 3, 16)).astype(np.float32)
        v = rng.normal(size=(2, 64, 3, 16)).astype(np.float32)
        out = blockwise_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            causal=causal, window=window, q_chunk=chunk, kv_chunk=chunk,
        )
        ref = dense_attention_ref(q, k, v, causal, window)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)

    def test_kv_mask(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
        mask = jnp.asarray(np.arange(16) < 8)[None]
        out = blockwise_attention(q, k, v, causal=False, kv_seq_mask=mask, q_chunk=8, kv_chunk=8)
        # identical to attending over the first 8 kv only
        ref = dense_attention_ref(
            np.asarray(q), np.asarray(k[:, :8]), np.asarray(v[:, :8]), causal=False
        )
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


class TestSSD:
    @pytest.mark.parametrize("chunk", [8, 16, 64])
    def test_matches_naive_recurrence(self, chunk):
        rng = np.random.default_rng(0)
        b, l, h, p, n = 2, 64, 3, 8, 16
        x = rng.normal(size=(b, l, h, p)).astype(np.float32)
        dt = rng.uniform(0.01, 0.2, size=(b, l, h)).astype(np.float32)
        A = -np.exp(rng.normal(size=(h,)).astype(np.float32))
        B = rng.normal(size=(b, l, h, n)).astype(np.float32)
        C = rng.normal(size=(b, l, h, n)).astype(np.float32)
        y, final = ssd_chunked(
            jnp.asarray(x * dt[..., None]), jnp.asarray(dt * A),
            jnp.asarray(B), jnp.asarray(C), chunk=chunk,
        )
        state = np.zeros((b, h, p, n))
        ys = []
        for t in range(l):
            state = state * np.exp(dt[:, t] * A)[..., None, None] + np.einsum(
                "bhp,bhn->bhpn", x[:, t] * dt[:, t][..., None], B[:, t]
            )
            ys.append(np.einsum("bhpn,bhn->bhp", state, C[:, t]))
        np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(final), state, rtol=1e-4, atol=1e-5)

    def test_initial_state_continuation(self):
        """Splitting a sequence across two ssd calls == one call (prefill
        chunking invariant)."""
        rng = np.random.default_rng(2)
        b, l, h, p, n = 1, 32, 2, 4, 8
        x = jnp.asarray(rng.normal(size=(b, l, h, p)).astype(np.float32))
        dt = jnp.asarray(rng.uniform(0.05, 0.2, size=(b, l, h)).astype(np.float32))
        A = -jnp.exp(jnp.asarray(rng.normal(size=(h,)).astype(np.float32)))
        B = jnp.asarray(rng.normal(size=(b, l, h, n)).astype(np.float32))
        C = jnp.asarray(rng.normal(size=(b, l, h, n)).astype(np.float32))
        xd, dA = x * dt[..., None], dt * A
        y_full, s_full = ssd_chunked(xd, dA, B, C, chunk=8)
        y1, s1 = ssd_chunked(xd[:, :16], dA[:, :16], B[:, :16], C[:, :16], chunk=8)
        y2, s2 = ssd_chunked(
            xd[:, 16:], dA[:, 16:], B[:, 16:], C[:, 16:], chunk=8, initial_state=s1
        )
        np.testing.assert_allclose(np.asarray(y_full[:, 16:]), np.asarray(y2), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2), rtol=1e-4, atol=1e-5)


class TestDecodeConsistency:
    @pytest.mark.parametrize("name", ["llama3.2-1b", "mamba2-780m", "jamba-1.5-large-398b"])
    def test_decode_matches_forward(self, name):
        cfg = ARCHS[name].reduced()
        params = MDL.init(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(0)
        S = 16
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, S)), jnp.int32)
        batch = {"tokens": toks, "targets": toks, "sample_mask": jnp.ones((1,), bool)}
        x, _ = MDL._embed_inputs(cfg, params, batch)
        h, _ = MDL._trunk(cfg, params, x)
        full = np.asarray(MDL._logits(cfg, params, h))[0]
        cache = MDL.init_cache(cfg, 1, S)
        step = jax.jit(lambda p, c, t: MDL.decode_step(cfg, p, c, t))
        outs = []
        for t in range(S):
            lg, cache = step(params, cache, toks[:, t : t + 1])
            outs.append(np.asarray(lg[0, 0]))
        np.testing.assert_allclose(np.stack(outs), full, rtol=1e-3, atol=2e-4)


class TestMoE:
    def test_dispatch_slots_unique_and_bounded(self):
        rng = np.random.default_rng(0)
        e, cap = 4, 8
        ids = jnp.asarray(rng.integers(0, e, size=(24,)), jnp.int32)
        sort_idx, slots, keep = _dispatch_indices(ids, e, cap)
        slots = np.asarray(slots)[np.asarray(keep)]
        assert len(np.unique(slots)) == len(slots)  # no collisions among kept
        assert slots.max() < e * cap

    def test_capacity_overflow_dropped(self):
        ids = jnp.asarray(np.zeros(10, np.int32))  # all to expert 0
        _, _, keep = _dispatch_indices(ids, 4, 4)
        assert int(np.asarray(keep).sum()) == 4

    def test_moe_ffn_routes_all_tokens_at_high_capacity(self):
        """With capacity_factor high enough nothing is dropped; output must
        differ from zero for every token."""
        key = jax.random.PRNGKey(0)
        p = init_moe(key, 32, 64, 4, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        out, aux = moe_ffn(p, x, top_k=2, capacity_factor=4.0)
        assert out.shape == x.shape
        assert np.all(np.abs(np.asarray(out)).sum(-1) > 0)
        assert float(aux) > 0.5  # load-balance loss near 1 for uniform-ish routing

    def test_moe_grad_flows_to_router(self):
        key = jax.random.PRNGKey(0)
        p = init_moe(key, 16, 32, 4, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))

        def loss(p):
            out, aux = moe_ffn(p, x, top_k=2)
            return jnp.sum(out ** 2) + aux

        g = jax.grad(loss)(p)
        assert np.abs(np.asarray(g["router"])).sum() > 0


class TestXMLStoredRows:
    """w1 is stored with its row count rounded up to the float32 sublane
    tile (DESIGN.md §3); the padding rows are inert."""

    H, R = 16, 4

    @staticmethod
    def _batches(nf, n_rounds, b_slots=8):
        from repro.data.providers import SparseProvider
        from repro.data.xml_synth import make_xml_dataset

        ds = make_xml_dataset(n_samples=256, n_features=nf, n_classes=32,
                              avg_nnz=16, seed=0)
        prov = SparseProvider.make(ds, seed=1)
        # every replica's batch leaves sample slots (and so nnz slots) masked
        return [
            {k: jnp.asarray(v) for k, v in prov.stack(
                [prov.fetch(b_slots - 1 - r, b_slots) for r in range(4)]
            ).items()}
            for _ in range(n_rounds)
        ]

    @pytest.mark.parametrize("nf", [512, 509], ids=["aligned", "unaligned"])
    def test_init_pads_w1_with_zero_rows(self, nf):
        from repro.models.xml_mlp import XMLMLPConfig, init_params, stored_rows

        cfg = XMLMLPConfig(n_features=nf, n_classes=32, hidden=self.H)
        assert stored_rows(nf, jnp.float32) == -(-nf // 8) * 8
        assert stored_rows(nf, jnp.bfloat16) == -(-nf // 16) * 16
        key = jax.random.PRNGKey(7)
        w1 = np.asarray(init_params(cfg, key)["w1"])
        assert w1.shape == (-(-nf // 8) * 8, self.H)
        k1, _ = jax.random.split(key)
        want = jax.random.normal(k1, (nf, self.H)) * (1.0 / jnp.sqrt(nf))
        np.testing.assert_array_equal(w1[:nf], np.asarray(want))
        assert not w1[nf:].any()

    @pytest.mark.parametrize("nf", [512, 509], ids=["aligned", "unaligned"])
    def test_padding_rows_stay_zero_and_sparse_matches_dense(self, nf):
        """Three R=4 rounds of the sparse path (one replica masked out in
        the second) against the dense ``loss_fn`` oracle."""
        from repro.models.xml_mlp import (
            XMLMLPConfig, init_params, loss_and_sparse_grad, loss_fn,
        )
        from repro.optim.sgd import SGDConfig, sgd_update
        import repro.utils.tree as tu

        cfg = XMLMLPConfig(n_features=nf, n_classes=32, hidden=self.H)
        reps = tu.tree_broadcast_replicas(
            init_params(cfg, jax.random.PRNGKey(0)), self.R)
        lr = jnp.asarray([0.5, 0.4, 0.3, 0.2])

        @functools.partial(jax.jit, static_argnums=3)
        def step(p, batch, mask, sparse):
            if sparse:
                (loss, _), g = jax.vmap(
                    lambda q, b: loss_and_sparse_grad(cfg, q, b))(p, batch)
            else:
                (loss, _), g = jax.vmap(jax.value_and_grad(
                    lambda q, b: loss_fn(cfg, q, b), has_aux=True))(p, batch)
            new, _ = sgd_update(p, g, lr, SGDConfig(), update_mask=mask,
                                replica_dim=True)
            return new, loss

        batches = self._batches(nf, 3)
        # masked nnz slots point one past the *stored* rows: dropped by the
        # scatter, and the gradient densifies to w1's stored shape
        g = loss_and_sparse_grad(
            cfg, tu.tree_replica_slice(reps, 0),
            {k: v[0] for k, v in batches[0].items()})[1]["w1"]
        masked = ~np.asarray(batches[0]["feat_mask"][0]).reshape(-1)
        assert masked.any()
        assert (np.asarray(g.rows)[masked] == reps["w1"].shape[1]).all()
        assert g.densify().shape == reps["w1"].shape[1:]
        masks = [jnp.ones(self.R), jnp.asarray([1.0, 0.0, 1.0, 1.0]),
                 jnp.ones(self.R)]
        runs = {}
        for sparse in (True, False):
            p, losses = reps, []
            for batch, mask in zip(batches, masks):
                p, loss = step(p, batch, mask, sparse)
                losses.append(np.asarray(loss))
            runs[sparse] = (np.asarray(p["w1"]), np.stack(losses))
        (w_s, l_s), (w_d, l_d) = runs[True], runs[False]
        assert w_s.shape == (self.R, -(-nf // 8) * 8, self.H)
        assert not w_s[:, nf:].any() and not w_d[:, nf:].any()
        assert not np.array_equal(w_s[:, :nf], np.asarray(reps["w1"])[:, :nf])
        np.testing.assert_allclose(l_s, l_d, rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(w_s[:, :nf], w_d[:, :nf], rtol=1e-4,
                                   atol=1e-5)

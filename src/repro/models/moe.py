"""Mixture-of-Experts FFN: top-k router + sort-based dispatch + grouped matmul.

TPU-native formulation: instead of the (T, E, C) one-hot dispatch einsum
(memory O(T·E·C)), tokens are *sorted by expert id* and scattered into an
(E, C, D) buffer — O(T·k) bookkeeping + a grouped matmul that maps directly
onto the MXU (and onto the Pallas ``moe_gmm`` kernel). Experts are sharded
over the `model` (and optionally `data` = expert-parallel) mesh axes; GSPMD
turns the buffer reshard into the all-to-all of classic expert parallelism.

Router load-imbalance is the LM-world analogue of the paper's sparse-nnz
variance: per-batch expert counts fluctuate, so per-replica step time
fluctuates, giving Adaptive SGD's scheduler real signal (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.annotate import logical_axis_size, shard
from .layers import ninit, rmsnorm, split_keys


def init_moe(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
    dtype,
    dense_residual_ff: int = 0,
):
    kr, ki, kg, ko, kd = split_keys(key, 5)
    p = {
        "router": ninit(kr, (d_model, n_experts), d_model ** -0.5, jnp.float32),
        "wi": ninit(ki, (n_experts, d_model, d_ff), d_model ** -0.5, dtype),
        "wg": ninit(kg, (n_experts, d_model, d_ff), d_model ** -0.5, dtype),
        "wo": ninit(ko, (n_experts, d_ff, d_model), d_ff ** -0.5, dtype),
        "norm": jnp.zeros((d_model,), dtype),
    }
    if dense_residual_ff:
        k1, k2, k3 = split_keys(kd, 3)
        p["dense"] = {
            "wi": ninit(k1, (d_model, dense_residual_ff), d_model ** -0.5, dtype),
            "wg": ninit(k2, (d_model, dense_residual_ff), d_model ** -0.5, dtype),
            "wo": ninit(k3, (dense_residual_ff, d_model), dense_residual_ff ** -0.5, dtype),
        }
    return p


def _dispatch_indices(expert_ids: jax.Array, n_experts: int, capacity: int):
    """Sort-based slot assignment.

    expert_ids: (Tk,) int32. Returns (sort_idx, slots, keep) where
    ``slots[j]`` is the destination row in the (E*C) buffer for the j-th
    sorted assignment and ``keep`` masks capacity overflow.
    """
    tk = expert_ids.shape[0]
    sort_idx = jnp.argsort(expert_ids, stable=True)
    sorted_eids = expert_ids[sort_idx]
    counts = jnp.zeros((n_experts,), jnp.int32).at[expert_ids].add(1)
    starts = jnp.cumsum(counts) - counts  # first sorted position of each expert
    pos_in_expert = jnp.arange(tk, dtype=jnp.int32) - starts[sorted_eids]
    keep = pos_in_expert < capacity
    slots = sorted_eids * capacity + jnp.minimum(pos_in_expert, capacity - 1)
    return sort_idx, slots, keep


def _expert_ffn(params: dict, buf: jax.Array, use_gmm_kernel: bool) -> jax.Array:
    """Grouped SwiGLU over (E, C, D) capacity buffers."""
    if use_gmm_kernel:
        from repro.kernels.moe_gmm import ops as gmm_ops

        return gmm_ops.moe_ffn_gmm(buf, params["wi"], params["wg"], params["wo"])
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["wg"]))
    u = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    return jnp.einsum("ecf,efd->ecd", g * u, params["wo"])


def moe_ffn(
    params: dict,
    x: jax.Array,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    use_gmm_kernel: bool = False,
    dispatch: str = "global",
    force_groups: int = 0,
    combine_dtype: str = "f32",
) -> tuple[jax.Array, jax.Array]:
    """MoE FFN body (pre-norm residual added by caller).

    x: (B, S, D) normed input. Returns (out (B,S,D), aux_loss scalar).

    dispatch:
      * ``global``  — paper-era baseline: one argsort/gather/scatter over all
        T*k assignments. Under GSPMD with tokens sharded over the same axis
        as experts, the cross-shard scatter lowers to full-buffer
        all-reduces.
      * ``sharded`` — beyond-paper optimization (EXPERIMENTS.md §Perf):
        dispatch is computed *per token shard* (vmapped over G groups
        aligned with the batch sharding), so gathers/scatters stay local
        and the only cross-shard movement is the (G, E) -> (E, G) buffer
        reshard — the canonical expert-parallel all-to-all.
    """
    b, s, d = x.shape
    e = params["router"].shape[1]
    t = b * s
    h = x.reshape(t, d)

    logits = h.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, top_k)  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    pe = jnp.mean(probs, axis=0)
    fe = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(1.0) / (t * top_k)
    aux = e * jnp.sum(pe * fe)

    groups = 1
    if dispatch == "sharded":
        groups = force_groups if force_groups else logical_axis_size("experts")
        if t % groups or b % groups:
            groups = 1  # fall back (e.g. tiny smoke shapes)

    capacity = int(max(top_k, round(t // groups * top_k * capacity_factor / e)))

    def dispatch_group(h_g, ids_g, w_g):
        """One token shard: local sort-based dispatch into (E, C, D)."""
        flat = ids_g.reshape(-1).astype(jnp.int32)          # (Tg*k,)
        sort_idx, slots, keep = _dispatch_indices(flat, e, capacity)
        token_of = (sort_idx // top_k).astype(jnp.int32)
        buf = jnp.zeros((e * capacity, d), x.dtype)
        gathered = h_g[token_of] * keep[:, None].astype(x.dtype)
        buf = buf.at[slots].set(gathered, mode="drop")
        return buf.reshape(e, capacity, d), (sort_idx, slots, keep, token_of)

    # §Perf iteration 2: the combine path in f32 doubles the HBM and
    # collective bytes of every (T*k, D) tensor and its gradients; bf16
    # halves them (top_k<=8 partial sums stay well inside bf16 range).
    acc_dt = jnp.float32 if combine_dtype == "f32" else jnp.bfloat16

    def combine_group(out_buf_g, meta, w_g):
        sort_idx, slots, keep, token_of = meta
        tg = w_g.shape[0]
        out_rows = out_buf_g.reshape(e * capacity, d)[slots]
        w_sorted = w_g.reshape(-1)[sort_idx].astype(jnp.float32)
        contrib = out_rows.astype(acc_dt) * (w_sorted * keep)[:, None].astype(acc_dt)
        return jnp.zeros((tg, d), acc_dt).at[token_of].add(contrib)

    if groups == 1:
        buf, meta = dispatch_group(h, top_ids, top_w)
        buf = shard(buf, "experts", None, None)
        out_buf = _expert_ffn(params, buf, use_gmm_kernel)
        out_buf = shard(out_buf, "experts", None, None)
        y = combine_group(out_buf, meta, top_w)
    else:
        tg = t // groups
        h_g = h.reshape(groups, tg, d)
        ids_g = top_ids.reshape(groups, tg, top_k)
        w_g = top_w.reshape(groups, tg, top_k)
        buf_g, meta = jax.vmap(dispatch_group)(h_g, ids_g, w_g)  # (G,E,C,D)
        buf_g = shard(buf_g, "experts", None, None, None)  # G-dim local to shard
        # (G, E, C, D) -> (E, G*C, D): the expert-parallel all-to-all
        buf = buf_g.transpose(1, 0, 2, 3).reshape(e, groups * capacity, d)
        buf = shard(buf, "experts", None, None)
        out_buf = _expert_ffn(params, buf, use_gmm_kernel)
        out_buf = shard(out_buf, "experts", None, None)
        # back: (E, G*C, D) -> (G, E, C, D) — reverse all-to-all
        ob_g = out_buf.reshape(e, groups, capacity, d).transpose(1, 0, 2, 3)
        ob_g = shard(ob_g, "experts", None, None, None)
        y = jax.vmap(combine_group)(ob_g, meta, w_g).reshape(t, d)
    return y.reshape(b, s, d).astype(x.dtype), aux


def moe_ffn_gather(
    params: dict,
    x: jax.Array,
    *,
    top_k: int,
) -> tuple[jax.Array, jax.Array]:
    """Decode-time MoE FFN (§Perf pair 2, beyond-paper).

    For T = B*S ≪ E the capacity-buffer formulation reads ALL E experts'
    weights to serve a handful of tokens (useful fraction k/E). Here we
    *gather the k routed experts' weights per token* and compute densely:
    weight reads drop from E·(3·D·F) to T·k·(3·D·F) — a ~E/(T·k) reduction
    in the memory roofline term. Only sensible when T·k < E (decode);
    training keeps the buffer formulation (better MXU utilization).
    """
    b, s, d = x.shape
    t = b * s
    h = x.reshape(t, d)
    logits = h.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, top_k)  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    wi = params["wi"][top_ids]  # (T, k, D, F) — gathers only routed experts
    wg = params["wg"][top_ids]
    wo = params["wo"][top_ids]  # (T, k, F, D)
    g = jax.nn.silu(jnp.einsum("td,tkdf->tkf", h, wg))
    u = jnp.einsum("td,tkdf->tkf", h, wi)
    y = jnp.einsum("tkf,tkfd,tk->td", g * u, wo, top_w.astype(wo.dtype))
    return y.reshape(b, s, d).astype(x.dtype), jnp.zeros((), jnp.float32)


def moe_layer(
    params: dict,
    x: jax.Array,
    *,
    top_k: int,
    norm_eps: float = 1e-5,
    capacity_factor: float = 1.25,
    use_gmm_kernel: bool = False,
    dispatch: str = "global",
    combine_dtype: str = "f32",
) -> tuple[jax.Array, jax.Array]:
    """Pre-norm MoE block: x + moe(norm(x)) [+ dense residual branch (arctic)]."""
    h = rmsnorm(x, params["norm"], norm_eps)
    if dispatch == "gather":
        out, aux = moe_ffn_gather(params, h, top_k=top_k)
    else:
        out, aux = moe_ffn(
            params, h, top_k=top_k, capacity_factor=capacity_factor,
            use_gmm_kernel=use_gmm_kernel, dispatch=dispatch,
            combine_dtype=combine_dtype,
        )
    if "dense" in params:
        dp = params["dense"]
        g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, dp["wg"]))
        u = jnp.einsum("bsd,df->bsf", h, dp["wi"])
        out = out + jnp.einsum("bsf,fd->bsd", g * u, dp["wo"])
    return x + out, aux

"""Unified causal LM covering all assigned architecture families.

One config-driven model: dense / GQA attention, MoE FFN, Mamba2 (SSD)
mixers, hybrid interleaves (Jamba), encoder-decoder (Seamless), and
VLM/audio stub frontends. Layer stacks are grouped into (prefix, periodic
blocks) so homogeneous spans run under ``lax.scan`` — compile time and HLO
size stay bounded even for 72-layer hybrids.

API (consumed by the trainer and the launchers):
    init(cfg, rng) -> params
    loss_fn(cfg, params, batch) -> (loss, aux)
    prefill(cfg, params, batch) -> (logits, cache)
    decode_step(cfg, params, cache, tokens) -> (logits, cache)
    init_cache(cfg, batch, max_len, window) -> cache
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.protocol import TrainableModel
from repro.sharding.annotate import shard
from . import layers as L
from . import mamba2 as M
from . import moe as MOE

# --------------------------------------------------------------------------
# layer pattern -> (prefix, period) decomposition
# --------------------------------------------------------------------------


def layer_pattern(cfg: ModelConfig) -> list[tuple[str, str]]:
    return [(cfg.layer_kind(i), cfg.ffn_kind(i)) for i in range(cfg.n_layers)]


def find_prefix_period(pattern: list) -> tuple[int, int]:
    """Smallest (prefix, period) with pattern[prefix:] periodic."""
    n = len(pattern)
    for prefix in range(0, n):
        rest = pattern[prefix:]
        if not rest:
            return prefix, 1
        for period in (1, 2, 4, 8):
            if len(rest) % period:
                continue
            if all(rest[i] == rest[i % period] for i in range(len(rest))):
                return prefix, period
    return n, 1  # fully unrolled fallback


# --------------------------------------------------------------------------
# per-layer init / apply
# --------------------------------------------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _remat(cfg: ModelConfig, body):
    """Apply the configured activation-checkpoint policy to a scan body.

    'full' recomputes everything (lowest live memory); 'dots' saves matmul
    outputs and recomputes only elementwise chains — §Perf iteration for
    compute-bound small archs: most of remat's recompute FLOPs are dots, so
    saving them recovers nearly remat=off compute at a fraction of the live
    memory."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return jax.checkpoint(body)


def init_sublayers(cfg: ModelConfig, key, kind: str, ffn_kind: str) -> dict:
    kmix, kffn = jax.random.split(key)
    dt = _dtype(cfg)
    p: dict = {}
    if kind == "attn":
        p["mixer"] = L.init_attention(
            kmix, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, dt
        )
    else:  # ssm
        p["mixer"] = M.init_mamba2(
            kmix,
            cfg.d_model,
            expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state,
            conv=cfg.ssm_conv,
            dtype=dt,
        )
    if ffn_kind == "moe":
        p["ffn"] = MOE.init_moe(
            kffn,
            cfg.d_model,
            cfg.d_ff,
            cfg.n_experts,
            dt,
            dense_residual_ff=cfg.dense_residual_ff if cfg.dense_residual else 0,
        )
    elif cfg.d_ff > 0:
        p["ffn"] = L.init_mlp(kffn, cfg.d_model, cfg.d_ff, dt)
    return p


def apply_sublayers(
    cfg: ModelConfig,
    kind: str,
    ffn_kind: str,
    params: dict,
    x: jax.Array,
    *,
    window: int = 0,
    positions=None,
    cross: Optional[tuple] = None,  # (cross_params, encoder_memory)
) -> tuple[jax.Array, jax.Array]:
    """Train/prefill path: mixer -> [cross-attn] -> ffn. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        x = L.attention_layer(
            params["mixer"],
            x,
            n_rep=cfg.n_heads // cfg.n_kv_heads,
            rope_theta=cfg.rope_theta,
            window=window if window else cfg.sliding_window,
            positions=positions,
            norm_eps=cfg.norm_eps,
            use_flash=cfg.use_flash_kernel,
        )
    else:
        x = M.mamba2_forward(
            params["mixer"],
            x,
            head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state,
            chunk=cfg.ssm_chunk,
            norm_eps=cfg.norm_eps,
            use_kernel=cfg.use_ssd_kernel,
        )
    if cross is not None:
        cp, mem = cross
        k = jnp.einsum("bsd,dhk->bshk", mem, cp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", mem, cp["wv"])
        x = L.attention_layer(
            cp,
            x,
            n_rep=cfg.n_heads // cfg.n_kv_heads,
            rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps,
            cross_kv=(k, v),
        )
    if ffn_kind == "moe":
        x, aux = MOE.moe_layer(
            params["ffn"], x, top_k=cfg.top_k, norm_eps=cfg.norm_eps,
            dispatch=cfg.moe_dispatch, combine_dtype=cfg.moe_combine_dtype,
            use_gmm_kernel=cfg.use_gmm_kernel,
        )
    elif cfg.d_ff > 0:
        x = L.mlp_layer(params["ffn"], x, cfg.norm_eps)
    return x, aux


# --------------------------------------------------------------------------
# model init
# --------------------------------------------------------------------------


def init(cfg: ModelConfig, rng: jax.Array) -> dict:
    dt = _dtype(cfg)
    pattern = layer_pattern(cfg)
    prefix, period = find_prefix_period(pattern)
    n_groups = (cfg.n_layers - prefix) // period
    keys = L.split_keys(rng, 6)

    params: dict = {"embed": L.init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dt)}

    # prefix layers (unrolled)
    pk = L.split_keys(keys[1], max(prefix, 1))
    params["prefix"] = [
        init_sublayers(cfg, pk[i], *pattern[i]) for i in range(prefix)
    ]

    # periodic blocks (scanned): for each in-period position, stack n_groups inits
    blocks = {}
    bk = L.split_keys(keys[2], max(period, 1))
    for j in range(period):
        kind, ffn_kind = pattern[prefix + j]
        gks = jnp.stack(L.split_keys(bk[j], max(n_groups, 1)))
        blocks[f"pos{j}"] = jax.vmap(
            lambda k: init_sublayers(cfg, k, kind, ffn_kind)
        )(gks)
    params["blocks"] = blocks

    params["final_norm"] = jnp.zeros((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.ninit(
            keys[3], (cfg.vocab_size, cfg.d_model), cfg.d_model ** -0.5, dt
        )

    # encoder (enc-dec) — homogeneous attn+mlp stack, scanned
    if cfg.encoder_layers > 0:
        eks = jnp.stack(L.split_keys(keys[4], cfg.encoder_layers))
        params["encoder"] = {
            "layers": jax.vmap(lambda k: init_sublayers(cfg, k, "attn", "dense"))(eks),
            "norm": jnp.zeros((cfg.d_model,), dt),
        }
        # per-decoder-layer cross-attention
        ck = L.split_keys(keys[5], cfg.n_layers)
        params["cross"] = [
            L.init_attention(
                ck[i], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, dt
            )
            for i in range(cfg.n_layers)
        ]
    if cfg.frontend is not None:
        fk = jax.random.fold_in(keys[5], 7)
        params["frontend_proj"] = L.ninit(
            fk, (cfg.frontend_dim, cfg.d_model), cfg.frontend_dim ** -0.5, dt
        )
    return params


# --------------------------------------------------------------------------
# forward (train / prefill trunk)
# --------------------------------------------------------------------------


def _run_encoder(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    """Bidirectional encoder over (projected) frontend embeddings."""
    x = frames.astype(_dtype(cfg)) @ params["frontend_proj"]
    enc = params["encoder"]

    def body(x, lp):
        h = L.attention_layer(
            lp["mixer"],
            x,
            n_rep=cfg.n_heads // cfg.n_kv_heads,
            rope_theta=cfg.rope_theta,
            causal=False,
            norm_eps=cfg.norm_eps,
        )
        h = L.mlp_layer(lp["ffn"], h, cfg.norm_eps)
        return h, None

    if cfg.remat:
        body = _remat(cfg, body)
    x, _ = jax.lax.scan(body, x, enc["layers"])
    return L.rmsnorm(x, enc["norm"], cfg.norm_eps)


def _trunk(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    *,
    memory: Optional[jax.Array] = None,
    window: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Apply prefix + scanned blocks (+ interleaved cross-attn for enc-dec)."""
    pattern = layer_pattern(cfg)
    prefix, period = find_prefix_period(pattern)
    aux_total = jnp.zeros((), jnp.float32)

    cross_all = params.get("cross")

    for i in range(prefix):
        x, aux = apply_sublayers(
            cfg, *pattern[i], params["prefix"][i], x, window=window,
            cross=(cross_all[i], memory) if cross_all is not None else None,
        )
        aux_total = aux_total + aux

    n_groups = (cfg.n_layers - prefix) // period
    if n_groups > 0:
        xs = dict(params["blocks"])
        if cross_all is not None:
            # stack per-group cross params: cross layers prefix..n arranged (g, period)
            cross_rest = cross_all[prefix:]
            for j in range(period):
                xs[f"cross_pos{j}"] = jax.tree_util.tree_map(
                    lambda *ls: jnp.stack(ls), *cross_rest[j::period]
                )

        def body(carry, bp):
            x, aux_acc = carry
            for j in range(period):
                kind, ffn_kind = pattern[prefix + j]
                x, aux = apply_sublayers(
                    cfg, kind, ffn_kind, bp[f"pos{j}"], x, window=window,
                    cross=(bp[f"cross_pos{j}"], memory) if cross_all is not None else None,
                )
                aux_acc = aux_acc + aux
            return (x, aux_acc), None

        if cfg.remat:
            body = _remat(cfg, body)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), xs)
    return x, aux_total


def _embed_inputs(cfg: ModelConfig, params: dict, batch: dict):
    """Token (+frontend) embeddings. Returns (x, n_vis) where the first
    n_vis positions are non-text (excluded from the loss)."""
    tok = L.embed(params["embed"], batch["tokens"])
    if cfg.frontend == "vision":
        vis = batch["patch_embeds"].astype(tok.dtype) @ params["frontend_proj"]
        return jnp.concatenate([vis, tok], axis=1), vis.shape[1]
    return tok, 0


def _logits(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x, cfg.logits_softcap)
    logits = jnp.einsum(
        "bsd,vd->bsv", x.astype(jnp.float32), params["lm_head"].astype(jnp.float32)
    )
    if cfg.logits_softcap > 0:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits


def loss_fn(cfg: ModelConfig, params: dict, batch: dict):
    """Masked causal-LM cross entropy. aux = accuracy / n_valid / moe aux."""
    memory = None
    if cfg.encoder_layers > 0:
        memory = _run_encoder(cfg, params, batch["frames"])
    x, n_vis = _embed_inputs(cfg, params, batch)
    x = shard(x, "replica", "batch", "seq", None)
    x, moe_aux = _trunk(cfg, params, x, memory=memory)
    if n_vis:
        x = x[:, n_vis:]
    logits = _logits(cfg, params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = batch["targets"]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]  # (B,S)
    smask = batch["sample_mask"].astype(jnp.float32)[:, None]
    n_valid = jnp.sum(smask) * tgt.shape[1]
    loss = jnp.sum(nll * smask) / jnp.maximum(n_valid, 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == tgt) * smask) / jnp.maximum(n_valid, 1.0)
    total = loss + cfg.router_aux_coef * moe_aux
    return total, {"accuracy": acc, "n_valid": jnp.sum(smask), "moe_aux": moe_aux,
                   "ce_loss": loss}


# --------------------------------------------------------------------------
# serving: cache init / prefill / decode
# --------------------------------------------------------------------------


def _layer_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int):
    dt = _dtype(cfg)
    if kind == "attn":
        hd = cfg.resolved_head_dim
        return {
            "k": jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dt),
            "v": jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dt),
        }
    n_heads = (cfg.ssm_expand * cfg.d_model) // cfg.ssm_head_dim
    d_inner = n_heads * cfg.ssm_head_dim
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_inner + 2 * cfg.ssm_state), dt),
        "ssm": jnp.zeros((batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int, window: int = 0) -> dict:
    """window > 0 => rolling attention buffers of that size (long_500k path)."""
    pattern = layer_pattern(cfg)
    prefix, period = find_prefix_period(pattern)
    n_groups = (cfg.n_layers - prefix) // period
    attn_len = min(max_len, window) if window else max_len
    cache: dict = {
        "prefix": [
            _layer_cache(cfg, pattern[i][0], batch, attn_len) for i in range(prefix)
        ],
        "blocks": {},
        "cur_len": jnp.zeros((), jnp.int32),
    }
    for j in range(period):
        kind, _ = pattern[prefix + j]
        one = _layer_cache(cfg, kind, batch, attn_len)
        cache["blocks"][f"pos{j}"] = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l[None], (n_groups,) + l.shape), one
        )
    if cfg.encoder_layers > 0:
        hd = cfg.resolved_head_dim
        dt = _dtype(cfg)
        mem_len = cfg.frontend_len
        cache["cross_kv"] = [
            {
                "k": jnp.zeros((batch, mem_len, cfg.n_kv_heads, hd), dt),
                "v": jnp.zeros((batch, mem_len, cfg.n_kv_heads, hd), dt),
            }
            for _ in range(cfg.n_layers)
        ]
    return cache


def _decode_sublayers(
    cfg: ModelConfig,
    kind: str,
    ffn_kind: str,
    params: dict,
    x: jax.Array,
    cache: dict,
    cur_len,
    window: int,
    cross: Optional[tuple] = None,  # (cross_params, cross_kv_cache)
):
    if kind == "attn":
        x, ck, cv = L.decode_attention(
            params["mixer"],
            x,
            cache["k"],
            cache["v"],
            cur_len,
            n_rep=cfg.n_heads // cfg.n_kv_heads,
            rope_theta=cfg.rope_theta,
            window=window,
            norm_eps=cfg.norm_eps,
        )
        new_cache = {"k": ck, "v": cv}
    else:
        x, new_cache = M.mamba2_decode_step(
            params["mixer"],
            x,
            cache,
            head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state,
            norm_eps=cfg.norm_eps,
        )
    if cross is not None:
        cp, ckv = cross
        x, _, _ = L.decode_attention(
            cp, x, ckv["k"], ckv["v"], cur_len,
            n_rep=cfg.n_heads // cfg.n_kv_heads, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, cross=True,
        )
    if ffn_kind == "moe":
        decode_dispatch = "gather" if cfg.moe_decode_gather else cfg.moe_dispatch
        x, _ = MOE.moe_layer(params["ffn"], x, top_k=cfg.top_k,
                             norm_eps=cfg.norm_eps, dispatch=decode_dispatch,
                             combine_dtype=cfg.moe_combine_dtype)
    elif cfg.d_ff > 0:
        x = L.mlp_layer(params["ffn"], x, cfg.norm_eps)
    return x, new_cache


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,  # (B, 1)
    window: int = 0,
) -> tuple[jax.Array, dict]:
    """One-token decode against the cache. Returns (logits (B,1,V), cache)."""
    pattern = layer_pattern(cfg)
    prefix, period = find_prefix_period(pattern)
    cur = cache["cur_len"]
    x = L.embed(params["embed"], tokens)

    new_prefix = []
    for i in range(prefix):
        x, nc = _decode_sublayers(
            cfg, *pattern[i], params["prefix"][i], x, cache["prefix"][i], cur, window,
            cross=(params["cross"][i], cache["cross_kv"][i])
            if cfg.encoder_layers > 0 else None,
        )
        new_prefix.append(nc)

    cross_rest = None
    if cfg.encoder_layers > 0 and cfg.n_layers > prefix:
        cross_params_rest = params["cross"][prefix:]
        cross_cache_rest = cache["cross_kv"][prefix:]
        cross_rest = {}
        for j in range(period):
            cross_rest[f"p{j}"] = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *cross_params_rest[j::period]
            )
            cross_rest[f"c{j}"] = jax.tree_util.tree_map(
                lambda *ls: jnp.stack(ls), *cross_cache_rest[j::period]
            )

    def body(x, sc):
        bp, bc = sc["params"], sc["cache"]
        new_bc = {}
        for j in range(period):
            kind, ffn_kind = pattern[prefix + j]
            x, nc = _decode_sublayers(
                cfg, kind, ffn_kind, bp[f"pos{j}"], x, bc[f"pos{j}"], cur, window,
                cross=(sc["cross_p"][f"p{j}"], sc["cross_c"][f"c{j}"])
                if cross_rest is not None else None,
            )
            new_bc[f"pos{j}"] = nc
        return x, new_bc

    xs = {"params": params["blocks"], "cache": cache["blocks"]}
    if cross_rest is not None:
        xs["cross_p"] = {k: v for k, v in cross_rest.items() if k.startswith("p")}
        xs["cross_c"] = {k: v for k, v in cross_rest.items() if k.startswith("c")}
    n_groups = (cfg.n_layers - prefix) // period
    if n_groups > 0:
        x, new_blocks = jax.lax.scan(body, x, xs)
    else:
        new_blocks = cache["blocks"]

    logits = _logits(cfg, params, x)
    new_cache = dict(cache)
    new_cache["prefix"] = new_prefix
    new_cache["blocks"] = new_blocks
    new_cache["cur_len"] = cur + 1
    return logits, new_cache


def prefill(cfg: ModelConfig, params: dict, batch: dict):
    """Full-sequence forward returning last-position logits.

    (Prefill cache extraction is exercised via decode_step-based
    serving.)
    """
    memory = None
    if cfg.encoder_layers > 0:
        memory = _run_encoder(cfg, params, batch["frames"])
    x, n_vis = _embed_inputs(cfg, params, batch)
    x, _ = _trunk(cfg, params, x, memory=memory)
    return _logits(cfg, params, x[:, -1:, :])


# --------------------------------------------------------------------------
# trainer-protocol bundle
# --------------------------------------------------------------------------


def make_model(cfg: ModelConfig) -> TrainableModel:
    return TrainableModel(
        init=lambda rng: init(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch),
        config=cfg,
    )

"""Random model inputs for smoke tests — one source of truth for the LM
batch layouts.

Batch layouts per mode (leading replica dim R added by the caller):
  train   : tokens/targets (B, S) int32, sample_mask (B,) bool
            [+ patch_embeds (B, P, Fd) for vlm; frames (B, F, Fd) for audio]
  decode  : tokens (B, 1) int32 + KV/SSM cache of seq_len context
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as MDL


def _frontend_fields(cfg: ModelConfig, b: int, rng) -> dict:
    """Random frontend embeddings of the vision and audio archs."""
    name = {"vision": "patch_embeds", "audio": "frames"}.get(cfg.frontend)
    if name is None:
        return {}
    shape = (b, cfg.frontend_len, cfg.frontend_dim)
    return {name: jax.random.normal(rng, shape, jnp.float32)}


def make_train_batch(cfg: ModelConfig, b: int, s: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, size=(b, s + 1), dtype=np.int32)
    batch = {
        "tokens": jnp.asarray(toks[:, :-1]),
        "targets": jnp.asarray(toks[:, 1:]),
        "sample_mask": jnp.ones((b,), jnp.bool_),
    }
    key = jax.random.PRNGKey(seed)
    batch.update(_frontend_fields(cfg, b, key))
    return batch


def make_decode_inputs(cfg: ModelConfig, b: int, context: int, window: int = 0, seed: int = 0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(b, 1), dtype=np.int32))
    cache = MDL.init_cache(cfg, b, context, window)
    cache["cur_len"] = jnp.asarray(context - 1, jnp.int32)
    return tokens, cache

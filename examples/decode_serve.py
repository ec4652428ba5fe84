"""Serving example: batched greedy decoding with a KV/SSM cache for three
architecture families (dense GQA, attention-free Mamba2, hybrid Jamba) in
their reduced configurations, through ``decode_step``.

Run:  PYTHONPATH=src python examples/decode_serve.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import ARCHS
from repro.models import model as MDL

ARCHES = ["llama3.2-1b", "mamba2-780m", "jamba-1.5-large-398b"]
BATCH, CONTEXT, GEN = 2, 16, 8


def serve(arch: str):
    cfg = ARCHS[arch].reduced()
    params = MDL.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    cache = MDL.init_cache(cfg, BATCH, CONTEXT + GEN)
    step = jax.jit(lambda p, c, t: MDL.decode_step(cfg, p, c, t))

    # prefill by stepping through the prompt
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (BATCH, CONTEXT)), jnp.int32
    )
    logits = None
    for i in range(CONTEXT):
        logits, cache = step(params, cache, prompt[:, i : i + 1])

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(GEN - 1):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    toks = jnp.concatenate(out, axis=1)
    assert toks.shape == (BATCH, GEN)
    assert not bool(jnp.any(jnp.isnan(logits)))
    kinds = {MDL.layer_pattern(cfg)[i][0] for i in range(cfg.n_layers)}
    return toks, (GEN - 1) / dt, kinds


def main():
    print(f"batch={BATCH} context={CONTEXT} generate={GEN}\n")
    for arch in ARCHES:
        toks, sps, kinds = serve(arch)
        print(f"{arch:<24} mixers={sorted(kinds)!s:<18} "
              f"decode {sps:6.1f} steps/s  sample={np.asarray(toks[0, :6])}")
    print("\nAll three families decode through the same serve path "
          "(KV cache for attn, O(1) recurrent state for SSM layers).")


if __name__ == "__main__":
    main()

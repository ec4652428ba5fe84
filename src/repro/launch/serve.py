"""Serving launcher: batched prefill + decode for any assigned architecture.

Prefill builds the KV/SSM cache for a batch of prompts, then
``decode_step`` generates tokens autoregressively (one token per step,
cache updated in place functionally).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --batch 4 --context 64 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import ARCHS
from repro.models import model as MDL
from repro.utils.logging import log


def greedy_generate(cfg, params, prompt_tokens, gen_len: int, window: int = 0):
    """Prefill via repeated decode_step over the prompt (teacher-forced),
    then greedy generation. Returns (generated (B, gen_len), steps/s)."""
    b, prompt_len = prompt_tokens.shape
    cache = MDL.init_cache(cfg, b, prompt_len + gen_len, window)

    step = jax.jit(lambda p, c, t: MDL.decode_step(cfg, p, c, t, window=window))

    # prefill: feed prompt tokens one at a time (cache-consistent path)
    logits = None
    for i in range(prompt_len):
        logits, cache = step(params, cache, prompt_tokens[:, i : i + 1])

    out = []
    t0 = time.perf_counter()
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    for _ in range(gen_len):
        out.append(tok)
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    return jnp.concatenate(out, axis=1), gen_len / dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--context", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--window", type=int, default=0,
                    help=">0: rolling-buffer sliding-window decode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(args.seed)
    params = MDL.init(cfg, jax.random.PRNGKey(args.seed))
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(args.batch, args.context)),
        jnp.int32,
    )
    toks, sps = greedy_generate(cfg, params, prompts, args.gen, window=args.window)
    assert toks.shape == (args.batch, args.gen)
    assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab_size)))
    log("serve", arch=cfg.name, batch=args.batch, context=args.context,
        generated=args.gen, decode_steps_per_s=round(sps, 2))
    return toks


if __name__ == "__main__":
    main()

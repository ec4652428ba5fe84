"""Pure-jnp oracle for the weighted model merge (Algorithm 2, line 11).

out = sum_r alphas[r] * replicas[r]  (+ gamma * (g - gp) when provided)

Shapes: replicas (R, N), one param leaf flattened per replica; the kernel
merges each leaf in its own stored shape, and tests compare it with this
oracle on the flattened view.
"""
from __future__ import annotations

import jax.numpy as jnp


def weighted_merge_ref(replicas, alphas, g=None, gp=None, gamma: float = 0.0):
    acc = jnp.einsum(
        "r,rn->n", alphas.astype(jnp.float32), replicas.astype(jnp.float32)
    )
    if g is not None and gamma != 0.0:
        acc = acc + gamma * (g.astype(jnp.float32) - gp.astype(jnp.float32))
    return acc.astype(replicas.dtype)

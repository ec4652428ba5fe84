"""Plain reference of the trained job: the model family's loss and
gradients (``families/<family>.py``: ``init_params``, ``loss``, ``pack``),
dense SGD, Algorithm 1 (batch size scaling) and Algorithm 2 (normalized
merge with perturbation and global-model momentum) of arXiv:2110.07029, in
straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made: the
weights come from the configuration's stated initialization and the seed,
the batches from the benchmark's own data and the plan's sample ids (which
replica trained on which samples in which round, the feed the program was
given). ``dtype`` other than float32 gives the control: the same reference
computed in a lower precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(tree):
    return jax.tree_util.tree_leaves(tree)[0].dtype


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("loss",))
def _round(replicas, batch, lr, live, loss):
    """One lockstep round over the replicas: plain SGD on each live one."""

    def one(p, b, lr_i, live_i):
        value, g = jax.value_and_grad(loss)(p, b)
        step = (lr_i * live_i).astype(_dtype(p))
        return jax.tree_util.tree_map(lambda x, gx: x - step * gx, p, g), value

    return jax.vmap(one)(replicas, batch, lr, live)


@jax.jit
def _replica_norms(replicas):
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
        for l in jax.tree_util.tree_leaves(replicas)
    ))


@jax.jit
def _merge(replicas, alphas, g, gp, gamma):
    dtype = _dtype(replicas)
    return jax.tree_util.tree_map(
        lambda r, a, b: (
            jnp.tensordot(alphas.astype(dtype), r, axes=1)
            + (gamma * (a - b)).astype(dtype)
        ),
        replicas, g, gp,
    )


@jax.jit
def _delta_norms(params, params0):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        params[k].astype(jnp.float32) - params0[k].astype(jnp.float32))))
        for k in params}


def batch_size_scaling(b, lr, u, b_min, b_max, beta):
    """Algorithm 1."""
    b, lr, u = (np.asarray(x, np.float64).copy() for x in (b, lr, u))
    mu = u.mean()
    for i in range(len(b)):
        if u[i] > mu and b[i] + beta * (u[i] - mu) <= b_max:
            new_b = b[i] + beta * (u[i] - mu)
        elif u[i] < mu and b[i] - beta * (mu - u[i]) >= b_min:
            new_b = b[i] - beta * (mu - u[i])
        else:
            continue
        lr[i] *= new_b / b[i]
        b[i] = new_b
    return b, lr


def merge_weights(u, b, norms_per_param, pert_thr, delta):
    """Algorithm 2, lines 1-10: weights from update counts (batch sizes
    when all counts agree), then the perturbation of the most and least
    updated replicas when every replica is regularized."""
    u = np.asarray(u, np.float64)
    b = np.asarray(b, np.float64)
    alphas = b / b.sum() if np.all(u == u[0]) else u / u.sum()
    if len(alphas) > 1 and np.all(norms_per_param < pert_thr):
        r, s = int(np.argmax(u)), int(np.argmin(u))
        if r != s:
            alphas[r] *= 1.0 + delta
            alphas[s] *= 1.0 - delta
    return alphas


def run(family, config: dict, traffic: dict, data, grids: list, seed: int,
        checked: tuple = (1, 3), dtype=jnp.float32) -> dict:
    """Train the plan's first ``len(grids)`` mega-batches of the model of
    ``family`` (its module) on ``data`` (what its ``make_data`` made).

    ``grids[m][r][i]`` holds the sample ids replica ``i`` trained on in
    round ``r`` of mega-batch ``m`` (None: no batch). Returns each
    mega-batch's training loss (the mean over live rounds of the mean over
    live replicas), after each mega-batch count in ``checked`` the norm of
    each leaf's change of the global model since the start, and each leaf's
    norm at the start.
    """
    alg = traffic["algorithm"]
    n_rep = 1 if alg == "single" else int(traffic["replicas"])
    b_max = int(traffic["b_max"])
    params0 = family.init_params(seed, config, dtype)
    replicas = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape), params0)
    g = gp = params0
    b = np.full(n_rep, float(b_max))
    lr = traffic["lr"] * b / b_max
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params0))
    losses, deltas = [], {}
    for m, grid in enumerate(grids, start=1):
        u = np.array([sum(row[i] is not None for row in grid) for i in range(n_rep)])
        round_losses = []
        for row in grid:
            live = np.array([p is not None for p in row], np.float32)
            if not live.any():
                continue
            batch = family.pack(data, row, b_max)
            replicas, values = _round(replicas, batch, jnp.asarray(lr, jnp.float32),
                                      jnp.asarray(live), loss=family.loss)
            values = np.asarray(values, np.float64)
            round_losses.append(float((values * live).sum() / live.sum()))
        losses.append(float(np.mean(round_losses)))
        if alg == "adaptive":
            norms = np.asarray(_replica_norms(replicas), np.float64) / n_params
            alphas = merge_weights(u, b, norms, traffic["pert_thr"], traffic["delta"])
            merged = _merge(replicas, jnp.asarray(alphas, jnp.float32), g, gp,
                            jnp.float32(traffic["gamma"]))
            gp, g = g, merged
            replicas = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape), merged)
            b, lr = batch_size_scaling(b, lr, u, traffic["b_min"], b_max,
                                       traffic["beta"])
        elif alg == "single":
            g = jax.tree_util.tree_map(lambda x: x[0], replicas)
        else:
            raise ValueError(f"the reference has no algorithm {alg!r}")
        if m in checked:
            deltas[m] = {k_: float(v) for k_, v in _delta_norms(g, params0).items()}
    norms0 = {k_: float(v) for k_, v in _delta_norms(
        params0, jax.tree_util.tree_map(jnp.zeros_like, params0)).items()}
    return {"losses": losses, "deltas": deltas, "norms0": norms0}

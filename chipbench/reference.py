"""Plain reference of the trained job: the sparse MLP's loss and gradients in
straightforward ``jax.numpy`` at ``HIGHEST`` matmul precision, dense SGD,
Algorithm 1 (batch size scaling) and Algorithm 2 (normalized merge with
perturbation and global-model momentum) of arXiv:2110.07029.

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

def init_params(seed: int, n_features: int, n_classes: int, hidden: int,
                dtype=jnp.float32) -> dict:
    """w1 ~ N(0, 1/n_features) and w2 ~ N(0, 1/hidden) from the two halves
    of ``split(PRNGKey(seed))``; biases zero (the configuration's ``init``)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    w1 = jax.random.normal(k1, (n_features, hidden), jnp.float32)
    w2 = jax.random.normal(k2, (hidden, n_classes), jnp.float32)
    return {
        "w1": (w1 * (1.0 / jnp.sqrt(n_features))).astype(dtype),
        "b1": jnp.zeros((hidden,), dtype),
        "w2": (w2 * (1.0 / jnp.sqrt(hidden))).astype(dtype),
        "b2": jnp.zeros((n_classes,), dtype),
    }


def loss(params: dict, batch: dict):
    """Mean over valid samples of the mean over each sample's labels of
    -log softmax; the input layer is a gather of W1 rows weighted by the
    slot values."""
    dtype = params["w1"].dtype
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    scale = (batch["feat_val"] * batch["feat_mask"]).astype(dtype)
    rows = params["w1"][batch["feat_idx"]]
    h = jax.nn.relu(
        jnp.einsum("bk,bkh->bh", scale, rows, precision=prec) + params["b1"]
    )
    logits = jnp.dot(h, params["w2"], precision=prec) + params["b2"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    lab = jnp.take_along_axis(logp, batch["label_idx"], axis=-1)
    lmask = batch["label_mask"].astype(dtype)
    per_sample = -jnp.sum(lab * lmask, axis=-1) / jnp.maximum(
        jnp.sum(lmask, axis=-1), 1
    )
    smask = batch["sample_mask"].astype(dtype)
    return jnp.sum(per_sample * smask) / jnp.maximum(jnp.sum(smask), 1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _round(replicas, batch, lr, live):
    """One lockstep round over the replicas: plain SGD on each live one."""

    def one(p, b, lr_i, live_i):
        value, g = jax.value_and_grad(loss)(p, b)
        step = (lr_i * live_i).astype(p["w1"].dtype)
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
    dtype = replicas["w1"].dtype
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


def pack(csr: dict, ids_per_replica: list, b_max: int, k: int, n_lab: int) -> dict:
    """(R, b_max, ...) padded batches; a replica with ``None`` gets an empty
    batch. A sample keeps its first ``k`` features and ``n_lab`` labels."""
    r = len(ids_per_replica)
    out = {
        "feat_idx": np.zeros((r, b_max, k), np.int32),
        "feat_val": np.zeros((r, b_max, k), np.float32),
        "feat_mask": np.zeros((r, b_max, k), bool),
        "label_idx": np.zeros((r, b_max, n_lab), np.int32),
        "label_mask": np.zeros((r, b_max, n_lab), bool),
        "sample_mask": np.zeros((r, b_max), bool),
    }
    for i, ids in enumerate(ids_per_replica):
        for row, sid in enumerate(() if ids is None else ids):
            s, e = csr["indptr"][sid], csr["indptr"][sid + 1]
            n = min(e - s, k)
            out["feat_idx"][i, row, :n] = csr["indices"][s:s + n]
            out["feat_val"][i, row, :n] = csr["values"][s:s + n]
            out["feat_mask"][i, row, :n] = True
            s, e = csr["label_ptr"][sid], csr["label_ptr"][sid + 1]
            n = min(e - s, n_lab)
            out["label_idx"][i, row, :n] = csr["labels"][s:s + n]
            out["label_mask"][i, row, :n] = True
            out["sample_mask"][i, row] = True
    return out


def run(config: dict, traffic: dict, csr: dict, k: int, n_lab: int,
        grids: list, seed: int, checked: tuple = (1, 3), dtype=jnp.float32) -> dict:
    """Train the plan's first ``len(grids)`` mega-batches.

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
    params0 = init_params(seed, config["n_features"], config["n_classes"],
                          config["hidden"], dtype)
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
            batch = pack(csr, row, b_max, k, n_lab)
            replicas, values = _round(replicas, batch, jnp.asarray(lr, jnp.float32),
                                      jnp.asarray(live))
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

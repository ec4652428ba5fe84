"""Algorithms 1 & 2 of the paper.

* ``batch_size_scaling`` — Algorithm 1 (host-side, numpy): rescale each
  replica's batch size and learning rate by its deviation from the mean
  update count.
* ``merge_weights`` / ``apply_perturbation`` — Algorithm 2's normalization
  and perturbation of the merge weights (host-side).
* ``normalized_merge`` — Algorithm 2's model update (jit-compatible jnp):
  weighted average of replicas + global-model momentum.

Host/device split: the weight *scalars* are tiny and depend on scheduler
bookkeeping (update counts), so they are computed on host; the O(|w|) tensor
math is jitted and runs sharded (the weighted reduction over the replica-
sharded leading dim lowers to the all-reduce merge of the paper's §4).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ElasticConfig
from repro.utils import tree as tu

PyTree = Any


# --------------------------------------------------------------------------
# Algorithm 1: Batch Size Scaling
# --------------------------------------------------------------------------


def batch_size_scaling(
    b: np.ndarray, lr: np.ndarray, u: np.ndarray, cfg: ElasticConfig
) -> tuple[np.ndarray, np.ndarray]:
    """One application of Algorithm 1.

    b, lr, u: per-replica batch size, learning rate, update count since the
    last merge. Returns updated (b, lr). Faster replicas (u_i > mean) get
    larger batches; slower ones smaller; lr follows the linear-scaling rule.
    """
    b = np.asarray(b, np.float64).copy()
    lr = np.asarray(lr, np.float64).copy()
    u = np.asarray(u, np.float64)
    mu = u.mean()  # line 1
    for i in range(len(b)):
        if u[i] > mu and b[i] + cfg.beta * (u[i] - mu) <= cfg.b_max:  # line 3
            new_b = b[i] + cfg.beta * (u[i] - mu)
            lr[i] = lr[i] * new_b / b[i]  # line 4
            b[i] = new_b  # line 5
        elif u[i] < mu and b[i] - cfg.beta * (mu - u[i]) >= cfg.b_min:  # line 6
            new_b = b[i] - cfg.beta * (mu - u[i])
            lr[i] = lr[i] * new_b / b[i]  # line 7
            b[i] = new_b  # line 8
    return b, lr


# --------------------------------------------------------------------------
# Algorithm 2: Normalized Model Merging
# --------------------------------------------------------------------------


def merge_weights(u: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Lines 1-6: alpha_i from update counts (if they differ) else batch sizes."""
    u = np.asarray(u, np.float64)
    b = np.asarray(b, np.float64)
    if np.all(u == u[0]):  # line 2: identical update counts
        alphas = b / b.sum()  # line 3
    else:
        alphas = u / u.sum()  # line 5
    return alphas


def apply_perturbation(
    alphas: np.ndarray,
    u: np.ndarray,
    replica_norms_per_param: np.ndarray,
    cfg: ElasticConfig,
) -> tuple[np.ndarray, bool]:
    """Lines 7-10: boost the most-updated replica when all are regularized.

    ``replica_norms_per_param`` = ||w_i||_2 / |w| for each replica.
    Returns (alphas, activated). Note the deliberate denormalization.
    """
    alphas = np.asarray(alphas, np.float64).copy()
    if len(alphas) < 2:
        return alphas, False
    if np.all(replica_norms_per_param < cfg.pert_thr):  # line 7
        r = int(np.argmax(u))  # line 8
        s = int(np.argmin(u))
        if r != s:
            alphas[r] *= 1.0 + cfg.delta  # line 9
            alphas[s] *= 1.0 - cfg.delta
            return alphas, True
    return alphas, False


def normalized_merge(
    replicas: PyTree,
    alphas,
    global_model: Optional[PyTree],
    prev_global: Optional[PyTree],
    gamma: float,
    use_kernel: Optional[bool] = None,
    axis_name: Optional[str] = None,
) -> PyTree:
    """Lines 11-12: w' = sum_i alpha_i w_i + gamma (w̄ - w̄_p).

    ``replicas`` leaves have a leading replica dim R (sharded over the
    replica mesh axis at scale). Returns the new global model w'.
    When global/prev are None (memory-lean mode for the >=398B archs, paper
    §4 "it can even be done directly on the model replicas"), the momentum
    term is skipped.

    ``use_kernel`` — route the O(|w|) tensor math through the fused
    weighted-merge Pallas kernel (kernels/weighted_merge): the R-way
    scale+add and the momentum term read every replica shard once from HBM.
    None = auto: kernel on accelerator backends, jnp on CPU (the fallback
    and differential oracle).

    ``axis_name`` — set when tracing inside the sharded replica executor
    (DESIGN.md §5): the local weighted sum over this shard's replicas
    (kernel or jnp — ``alphas`` is the local slice) is a *partial* of
    Algorithm 2's reduction, completed with a psum over the replica mesh
    axis before the momentum term; every shard then holds the replicated
    new global. This is exactly the paper §4 all-reduce merge.
    """
    with jax.named_scope("merge"):
        alphas = jnp.asarray(alphas, jnp.float32)
        if use_kernel is None:
            use_kernel = jax.default_backend() in ("tpu", "gpu")
        momentum = not (global_model is None or prev_global is None or gamma == 0.0)
        if use_kernel:
            from repro.kernels.weighted_merge.ops import merge_pytree

            if momentum and axis_name is None:
                # single-program path: weighted sum + momentum fused in-kernel
                return merge_pytree(replicas, alphas, global_model, prev_global, gamma)
            merged = merge_pytree(replicas, alphas)
        else:
            merged = tu.tree_weighted_sum_replicas(replicas, alphas)
        # per-shard partials -> the collective merge (momentum term must see
        # the complete weighted sum, so the psum sits between the two)
        merged = tu.tree_map(lambda l: tu.replica_all_sum(l, axis_name), merged)
        if not momentum:
            return merged
        return tu.tree_map(
            lambda m, g, gp: (
                m.astype(jnp.float32) + gamma * (g.astype(jnp.float32) - gp.astype(jnp.float32))
            ).astype(m.dtype),
            merged,
            global_model,
            prev_global,
        )


def replica_regularization(replicas: PyTree) -> np.ndarray:
    """||w_i||_2 / |w| per replica (feeds the line-7 condition)."""
    norms = tu.tree_l2_norm_per_replica(replicas)
    n_param = tu.tree_size(replicas) / norms.shape[0]
    return np.asarray(norms) / n_param

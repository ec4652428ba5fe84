"""SGD optimizer family used by the paper's local updates.

The paper's workers run plain mini-batch SGD locally (momentum lives at the
*global model* level inside Algorithm 2, not in the local update). We still
provide optional local momentum and weight decay for the production LM
configs. The API mirrors optax (init/update) but is replica-aware: the
learning rate may be a vector of shape (R,) broadcast against leaves with a
leading replica dimension — this is how the paper's *per-GPU learning rate*
(linear-scaling rule, Alg. 1 lines 4/7) is expressed on an SPMD machine.

Row-sparse gradients: a grad leaf may be a ``RowSparseGrad``
(optim/row_sparse.py) for a (..., NF, H) parameter; ``sgd_update`` then
scatters only the touched rows — O(S*H) instead of O(NF*H) — preserving
masked-lockstep and the per-replica lr broadcast. Semantics (DESIGN.md §3):

* plain SGD (momentum=0, weight_decay=0) is bit-comparable to densifying
  the gradient and running the dense update;
* weight decay is applied *lazily*: touched rows decay (exactly once per
  row, duplicates handled), untouched rows are not decayed that step;
* momentum is *lazy*: touched rows get the exact dense rule
  ``m' = mu*m + g``, untouched rows keep their momentum unchanged (dense
  SGD would decay it by ``mu`` and keep drifting the parameter);
* grad_clip densifies sparse leaves first (the global norm needs the
  duplicate-reduced gradient), so clipped configs pay the dense cost.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.optim.row_sparse import (
    RowSparseGrad,
    densify_tree,
    first_occurrence,
    is_row_sparse,
)

PyTree = Any


@dataclass(frozen=True)
class SGDConfig:
    momentum: float = 0.0
    nesterov: bool = False
    weight_decay: float = 0.0
    grad_clip: float = 0.0  # 0 = off; global-norm clip per replica


def init_momentum(params: PyTree, cfg: SGDConfig) -> Optional[PyTree]:
    if cfg.momentum == 0.0:
        return None
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def _broadcast_lr(lr, leaf):
    """lr may be scalar or (R,) matching the leaf's leading replica dim."""
    lr = jnp.asarray(lr, jnp.float32)
    if lr.ndim == 0:
        return lr
    return lr.reshape((-1,) + (1,) * (leaf.ndim - 1))


def clip_by_global_norm(grads: PyTree, max_norm: float, replica_dim: bool) -> PyTree:
    if max_norm <= 0.0:
        return grads
    leaves = jax.tree_util.tree_leaves(grads)
    if replica_dim:
        sq = sum(
            jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
            for l in leaves
        )
        norm = jnp.sqrt(sq)  # (R,)
        scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
        return jax.tree_util.tree_map(
            lambda l: (l.astype(jnp.float32) * scale.reshape((-1,) + (1,) * (l.ndim - 1))).astype(l.dtype),
            grads,
        )
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree_util.tree_map(lambda l: (l * scale).astype(l.dtype), grads)


# --------------------------------------------------------------------------
# per-leaf update rules
# --------------------------------------------------------------------------


def _dense_leaf_update(p, g, m, lr, cfg: SGDConfig, update_mask):
    """The original dense rule: wd -> momentum -> masked step."""
    if cfg.weight_decay:
        g = g + cfg.weight_decay * p.astype(g.dtype)
    new_m = None
    if m is not None:
        new_m = cfg.momentum * m + g.astype(m.dtype)
        g = g + cfg.momentum * new_m if cfg.nesterov else new_m
    lr_b = _broadcast_lr(lr, p)
    delta = lr_b * g.astype(jnp.float32)
    if update_mask is not None:
        delta = delta * update_mask.reshape((-1,) + (1,) * (p.ndim - 1))
    new_p = (p.astype(jnp.float32) - delta).astype(p.dtype)
    if new_m is not None and update_mask is not None:
        # frozen replicas must not accumulate momentum either
        new_m = jnp.where(
            update_mask.reshape((-1,) + (1,) * (new_m.ndim - 1)) > 0, new_m, m
        )
    return new_p, new_m


def _sparse_leaf_update(p, g: RowSparseGrad, m, lr, cfg: SGDConfig,
                        update_mask, replica_dim: bool):
    """Scatter-only update for a RowSparseGrad leaf (see module docstring).

    Out-of-bounds sentinel rows are dropped by the scatters; gathers at
    those slots clamp, but every gathered term is weighted by the
    ``first_occurrence`` mask, which is 0 there.
    """
    n_rows = g.n_rows
    lr_arr = jnp.asarray(lr, jnp.float32)

    def one(p1, rows, vals, m1, lr1, mk):
        vals = vals.astype(jnp.float32)
        first = None
        if cfg.weight_decay or m1 is not None:
            first = first_occurrence(rows, n_rows)[:, None]
        if cfg.weight_decay:  # lazy decay: touched rows, exactly once per row
            vals = vals + cfg.weight_decay * first * p1[rows].astype(jnp.float32)
        if m1 is not None:
            m32 = m1.astype(jnp.float32)
            # touched rows: m' = mu*m + sum(vals); mk=0 adds 0 (frozen)
            m_new = m32.at[rows].add(
                mk * ((cfg.momentum - 1.0) * first * m32[rows] + vals)
            )
            if cfg.nesterov:
                slot_delta = vals + cfg.momentum * first * m_new[rows]
            else:
                slot_delta = first * m_new[rows]
            new_m1 = m_new.astype(m1.dtype)
        else:
            slot_delta, new_m1 = vals, None
        new_p1 = p1.at[rows].add((-(lr1 * mk) * slot_delta).astype(p1.dtype))
        return new_p1, new_m1

    if not replica_dim:
        return one(p, g.rows, g.vals, m, lr_arr, 1.0)

    mask_arr = (
        jnp.ones(p.shape[0], jnp.float32)
        if update_mask is None
        else jnp.asarray(update_mask, jnp.float32)
    )
    lr_ax = 0 if lr_arr.ndim else None
    if m is None:
        mapped = jax.vmap(
            lambda p1, r1, v1, l1, k1: one(p1, r1, v1, None, l1, k1),
            in_axes=(0, 0, 0, lr_ax, 0),
        )
        new_p, _ = mapped(p, g.rows, g.vals, lr_arr, mask_arr)
        return new_p, None
    return jax.vmap(one, in_axes=(0, 0, 0, 0, lr_ax, 0))(
        p, g.rows, g.vals, m, lr_arr, mask_arr
    )


def sgd_update(
    params: PyTree,
    grads: PyTree,
    lr,
    cfg: SGDConfig = SGDConfig(),
    momentum_state: Optional[PyTree] = None,
    update_mask=None,
    replica_dim: bool = False,
):
    """One SGD step.

    ``update_mask`` — optional (R,) 0/1 vector implementing the *masked
    lockstep round*: replicas whose virtual clock has passed the mega-batch
    horizon keep their parameters unchanged (see core/scheduler.py).
    ``grads`` leaves may be RowSparseGrad (see module docstring).
    Returns (new_params, new_momentum_state).
    """
    if cfg.grad_clip > 0.0:
        grads = densify_tree(grads)  # clip norm needs the reduced gradient
        grads = clip_by_global_norm(grads, cfg.grad_clip, replica_dim)

    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    m_leaves = (
        treedef.flatten_up_to(momentum_state)
        if momentum_state is not None
        else [None] * len(p_leaves)
    )
    if update_mask is not None:
        update_mask = jnp.asarray(update_mask, jnp.float32)

    new_p, new_m = [], []
    for p, g, m in zip(p_leaves, g_leaves, m_leaves):
        if is_row_sparse(g):
            with jax.named_scope("sparse_update"):
                np_, nm_ = _sparse_leaf_update(
                    p, g, m, lr, cfg, update_mask, replica_dim
                )
        else:
            with jax.named_scope("dense_update"):
                np_, nm_ = _dense_leaf_update(p, g, m, lr, cfg, update_mask)
        new_p.append(np_)
        new_m.append(nm_)

    new_params = jax.tree_util.tree_unflatten(treedef, new_p)
    new_momentum = (
        jax.tree_util.tree_unflatten(treedef, new_m)
        if momentum_state is not None
        else None
    )
    return new_params, new_momentum

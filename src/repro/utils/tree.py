"""Pytree utilities used across the framework.

All functions are pure and jit-compatible unless noted. The elastic-averaging
core manipulates *replicated* pytrees whose leaves carry a leading replica
dimension ``R``; helpers here implement the per-replica reductions
(Algorithm 2 of the paper needs per-replica L2 norms and weighted sums).
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

PyTree = Any


def tree_map(fn: Callable, *trees: PyTree) -> PyTree:
    return jax.tree_util.tree_map(fn, *trees)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return tree_map(lambda x, y: x + y, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return tree_map(lambda x, y: x - y, a, b)


def tree_scale(a: PyTree, s) -> PyTree:
    return tree_map(lambda x: x * s, a)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y, leafwise."""
    return tree_map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_zeros_like(a: PyTree) -> PyTree:
    return tree_map(jnp.zeros_like, a)


def tree_size(a: PyTree) -> int:
    """Total number of scalar parameters in the tree (static python int)."""
    return sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(a))


def tree_dot(a: PyTree, b: PyTree):
    """Sum over leaves of <a_i, b_i>."""
    parts = jax.tree_util.tree_leaves(
        tree_map(lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)), a, b)
    )
    return jnp.sum(jnp.stack(parts))


def tree_l2_norm(a: PyTree):
    return jnp.sqrt(tree_dot(a, a))


def tree_l2_norm_per_replica(a: PyTree):
    """L2 norm per replica for a tree whose leaves have leading dim R.

    Returns a vector of shape (R,). Used by Algorithm 2's regularization
    check: ``||w_i||_2 / |w| < pert_thr``.
    """
    parts = [
        jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
        for l in jax.tree_util.tree_leaves(a)
    ]
    return jnp.sqrt(jnp.sum(jnp.stack(parts, axis=0), axis=0))


def tree_weighted_sum_replicas(a: PyTree, alphas) -> PyTree:
    """sum_i alphas[i] * a[i] over the leading replica dimension.

    ``alphas`` has shape (R,). This is the merge reduction of Algorithm 2,
    line 11 (without the momentum term).
    """

    def leaf(l):
        al = alphas.reshape((-1,) + (1,) * (l.ndim - 1)).astype(jnp.float32)
        return jnp.sum(al * l.astype(jnp.float32), axis=0).astype(l.dtype)

    return tree_map(leaf, a)


def spans_shards(axis_name: str | None) -> bool:
    """True when tracing over a replica mesh axis of more than one shard.

    A collective over a single shard is the identity; leaving it out keeps
    the one-device sharded program the vmap placement's program, op for op
    (so both fuse, and round, the same way).
    """
    return axis_name is not None and jax.lax.axis_size(axis_name) > 1


def replica_all_sum(x, axis_name: str | None = None):
    """Sum ``x`` over all shards of the replica mesh axis.

    ``axis_name=None`` (the vmap placement: every replica lives in this
    program) is the identity — local reductions over the leading R dim are
    already global. Under shard_map (``placement='sharded'``) the local R
    dim only covers this shard's replicas, and cross-replica math must
    psum the partials over the mesh axis.
    """
    return jax.lax.psum(x, axis_name) if spans_shards(axis_name) else x


def tree_replica_mean_keepdims(a: PyTree, axis_name: str | None = None) -> PyTree:
    """float32 mean over the *global* replica dim, keepdims, leafwise.

    The cross-replica averaging primitive of the sync/crossbow family.
    With ``axis_name`` set, each shard's local mean is pmean-ed over the
    replica mesh axis — exact because every shard owns the same number of
    replicas (sharding.rules.replica_mesh guarantees divisibility).
    """

    def leaf(l):
        m = jnp.mean(l.astype(jnp.float32), axis=0, keepdims=True)
        if spans_shards(axis_name):
            m = jax.lax.pmean(m, axis_name)
        return m

    return tree_map(leaf, a)


def tree_broadcast_replicas(a: PyTree, n: int) -> PyTree:
    """Broadcast a tree (no replica dim) to a leading replica dim of size n."""
    return tree_map(lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), a)


def tree_replica_slice(a: PyTree, i: int) -> PyTree:
    return tree_map(lambda l: l[i], a)


def tree_cast(a: PyTree, dtype) -> PyTree:
    return tree_map(lambda l: l.astype(dtype), a)


def tree_has_nan(a: PyTree):
    parts = [jnp.any(jnp.isnan(l)) for l in jax.tree_util.tree_leaves(a)]
    return jnp.any(jnp.stack(parts))

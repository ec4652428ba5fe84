"""Public entrypoint for the weighted-merge kernel.

``merge(replicas, alphas, ...)`` runs the Pallas kernel natively on TPU/GPU
and in interpret mode on CPU (CI): the kernel *body* runs in Python either
way, so correctness is validated on every platform. ``merge_pytree`` applies
the kernel leaf-wise over a replica-stacked param pytree, each leaf in the
layout the device stores it in; it is what ``asgd.normalized_merge`` routes
through on accelerator backends.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.experimental.layout import Layout

from .weighted_merge import reads_as_stored, weighted_merge


def _interpret_mode() -> bool:
    # Pallas lowers natively on TPU and GPU; only CPU needs interpret mode
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=None)
def stored_order(shape: tuple, dtype) -> tuple:
    """Major-to-minor dimension order of the layout the default device
    stores an array of ``shape`` and ``dtype`` in (row-major where the
    backend does not say). A TPU keeps ``(R, 128, C)`` with C not a multiple
    of 128 as ``(C, R, 128)``: order ``(2, 0, 1)``."""
    device = jax.devices()[0]
    try:
        pjrt = device.client.get_default_layout(np.dtype(dtype), shape, device)
    except jax.errors.JaxRuntimeError:
        return tuple(range(len(shape)))
    return tuple(Layout.from_pjrt_layout(pjrt).major_to_minor)


def merge(replicas, alphas, g=None, gp=None, gamma: float = 0.0):
    """replicas (R, *leaf); alphas (R,). Returns the merged leaf; for
    replicas (R, N), the (N,) vector."""
    return weighted_merge(
        replicas, alphas, g, gp, gamma,
        order=stored_order(replicas.shape, replicas.dtype),
        interpret=_interpret_mode(),
    )


def flat_leaves(replica_tree) -> int:
    """Leaves of a replica-stacked tree (arrays or shapes) that the merge
    cannot read as stored and flattens first (``reads_as_stored``)."""
    return sum(
        not reads_as_stored(x.shape, stored_order(x.shape, x.dtype), x.dtype)
        for x in jax.tree_util.tree_leaves(replica_tree)
    )


def merge_pytree(replica_tree, alphas, global_tree=None, prev_tree=None,
                 gamma: float = 0.0):
    """Leaf-wise Algorithm-2 merge over a pytree whose leaves carry a leading
    replica dim R. Returns a pytree shaped like one replica."""
    if global_tree is not None and gamma != 0.0:
        return jax.tree_util.tree_map(
            lambda x, g, gp: merge(x, alphas, g, gp, gamma),
            replica_tree, global_tree, prev_tree,
        )
    return jax.tree_util.tree_map(lambda x: merge(x, alphas), replica_tree)

"""Logical-axis sharding annotations.

Model code annotates activations with *logical* axis names; a sharding
context (installed by the launcher) maps them to mesh axes. Without a
context everything is a no-op, so the same model code runs in single-device
tests and under GSPMD.

Logical axes used across the zoo:
  replica   — elastic worker dim (paper's per-GPU model replicas)
  batch     — per-replica sample dim
  seq       — sequence dim
  embed     — d_model
  heads/kv_heads — attention heads
  ff        — MLP hidden
  vocab     — embedding/vocab rows
  experts   — MoE expert dim

Placement note (DESIGN.md §5): these annotations drive the *GSPMD* path
(a step jitted under a (data, model) mesh, as
tests/test_sharded_integration.py does), where the compiler partitions a
single program. The trainer's ``placement='sharded'`` replica executor is
the *manual* path — shard_map already fixes every leaf's layout via the
replica-axis specs in sharding/rules.py, so no sharding context is
installed there and ``shard()`` stays a no-op inside its traced bodies;
``replica_rules()`` below is the mapping the GSPMD entry points use when
only the replica dim is laid out. That separation is also what keeps
elastic membership (DESIGN.md §6) simple: when ``ElasticTrainer.resize``
swaps the replica mesh between mega-batches there is no installed context
to invalidate — only the trainer's own executor cache keys on the mesh. A
GSPMD entry point using ``sharding_context`` with ``replica_rules()`` must
instead re-enter the context with the new mesh after a resize.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX: dict = {"mesh": None, "rules": {}}


def set_context(mesh: Optional[Mesh], rules: Optional[dict]) -> None:
    _CTX["mesh"] = mesh
    _CTX["rules"] = dict(rules or {})


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules: dict):
    old = (_CTX["mesh"], _CTX["rules"])
    set_context(mesh, rules)
    try:
        yield
    finally:
        set_context(*old)


def replica_rules() -> dict:
    """Logical-axis mapping for a replica-only (1-D) mesh: the elastic
    replica dim shards over REPLICA_AXIS, everything else is replicated.
    The GSPMD counterpart of the trainer's shard_map specs."""
    from repro.sharding.rules import REPLICA_AXIS

    return {"replica": REPLICA_AXIS, "batch": None, "heads": None,
            "ff": None, "experts": None}


def logical_to_spec(axes: tuple, rules: Optional[dict] = None) -> P:
    rules = _CTX["rules"] if rules is None else rules
    out = []
    for a in axes:
        if a is None:
            out.append(None)
            continue
        m = rules.get(a)
        out.append(m)  # may be None, a mesh axis name, or a tuple of them
    return P(*out)


def logical_axis_size(name: str) -> int:
    """Mesh extent of the logical axis ``name`` under the current context
    (1 when no mesh / unmapped). Used by shard-local MoE dispatch to pick
    its group count."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return 1
    ax = _CTX["rules"].get(name)
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        out = 1
        for a in ax:
            out *= int(mesh.shape[a])
        return out
    return int(mesh.shape[ax])


def shard(x: jax.Array, *axes) -> jax.Array:
    """Constrain activation sharding by logical axis names (no-op w/o mesh).

    Safe under vmap: if the (traced) rank doesn't match the requested spec
    rank, the constraint is skipped rather than corrupting the program.
    """
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    if x.ndim == len(axes) - 1 and axes[0] == "replica":
        axes = axes[1:]  # serving paths carry no replica dim
    if x.ndim != len(axes):
        return x
    spec = logical_to_spec(axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

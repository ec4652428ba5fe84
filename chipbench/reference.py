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

Leaves are named by their tree path, the keys joined by ``/``
(``leaf_norms``): a flat dict keeps its keys, a nested tree reads
``blocks/pos0/attn/wq`` or ``prefix/0/mlp/wi``. Under the ``sharded``
placement the replicas sit one a chip as the program places them: the
reference builds its own 1-D mesh over the cell's devices, splits the
replica axis of the replicas and of each round's batch over it, and keeps
the global models replicated. The mesh only places; every replica's step
is the plain one of the ``vmap`` placement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

REPLICA = "replica"  # the axis of the reference's own mesh


def _dtype(tree):
    return jax.tree_util.tree_leaves(tree)[0].dtype


def leaf_norms(tree) -> dict:
    """The float32 norm of each leaf, named by its tree path with the keys
    joined by ``/``; traceable. A stacked leaf (a scanned group of layers)
    is one leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path, simple=True, separator="/"):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}


def delta_norms(params, params0) -> dict:
    """``leaf_norms`` of ``params - params0``, taken in float32."""
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, params0))


def _lockstep(replicas, batch, lr, live, loss):
    """One lockstep round over the replicas: plain SGD on each live one."""

    def one(p, b, lr_i, live_i):
        value, g = jax.value_and_grad(loss)(p, b)
        step = (lr_i * live_i).astype(_dtype(p))
        return jax.tree_util.tree_map(lambda x, gx: x - step * gx, p, g), value

    return jax.vmap(one)(replicas, batch, lr, live)


_round = functools.partial(jax.jit, donate_argnums=(0,),
                           static_argnames=("loss",))(_lockstep)


@functools.lru_cache(maxsize=None)
def _sharded_round(loss, mesh):
    """``_round`` with the replica axis split over ``mesh``: each chip runs
    the same vmapped step on its own replicas."""
    return jax.jit(jax.shard_map(
        functools.partial(_lockstep, loss=loss), mesh=mesh,
        in_specs=P(REPLICA), out_specs=P(REPLICA)), donate_argnums=(0,))


@jax.jit
def _replica_norms(replicas):
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim)))
        for l in jax.tree_util.tree_leaves(replicas)
    ))


def _weigh(replicas, alphas, g, gp, gamma, total=lambda x: x):
    """Algorithm 2's merge: the replicas weighed by ``alphas`` and summed
    (``total`` completes the sum across chips), plus the momentum term."""
    dtype = _dtype(replicas)
    return jax.tree_util.tree_map(
        lambda r, a, b: (
            total(jnp.tensordot(alphas.astype(dtype), r, axes=1))
            + (gamma * (a - b)).astype(dtype)
        ),
        replicas, g, gp,
    )


_merge = jax.jit(_weigh)


@functools.lru_cache(maxsize=None)
def _sharded_merge(mesh):
    """``_merge`` over replicas split across ``mesh``: each chip weighs its
    own, a sum across chips completes it; the global models, and the
    merged one, are replicated."""
    return jax.jit(jax.shard_map(
        functools.partial(_weigh, total=functools.partial(jax.lax.psum,
                                                          axis_name=REPLICA)),
        mesh=mesh, in_specs=(P(REPLICA), P(REPLICA), P(), P(), P()),
        out_specs=P()))


_delta_norms = jax.jit(delta_norms)
_leaf_norms = jax.jit(leaf_norms)


class Placement:
    """Where the reference keeps its replicas: all on the default device
    (``vmap``), or split one replica a chip over a mesh of the cell's
    devices (``sharded``: ``mesh`` set)."""

    def __init__(self, traffic: dict, n_rep: int, devices=None):
        self.n_rep = n_rep
        self.mesh = None
        self._broadcast = self._stack
        if traffic["placement"] != "sharded":
            return
        devices = list(jax.devices() if devices is None else devices)
        # the largest device count that divides the replicas, as the
        # program's replica mesh takes it
        n = max(d for d in range(1, min(n_rep, len(devices)) + 1)
                if n_rep % d == 0)
        self.mesh = Mesh(np.asarray(devices[:n]), (REPLICA,))
        self._split = NamedSharding(self.mesh, P(REPLICA))
        self._replicated = NamedSharding(self.mesh, P())
        self._broadcast = jax.jit(self._stack, out_shardings=self._split)

    def _stack(self, params):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (self.n_rep,) + x.shape), params)

    def split(self, tree):
        """``tree`` with its leading replica axis split over the mesh."""
        return tree if self.mesh is None else jax.device_put(tree, self._split)

    def replicated(self, tree):
        """``tree`` whole on every chip of the mesh."""
        return tree if self.mesh is None else jax.device_put(tree, self._replicated)

    def replicas(self, params):
        """``n_rep`` copies of ``params``, their replica axis placed."""
        return self._broadcast(params)

    def round(self, loss):
        return (functools.partial(_round, loss=loss) if self.mesh is None
                else _sharded_round(loss, self.mesh))

    def merge(self):
        return _merge if self.mesh is None else _sharded_merge(self.mesh)


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
        checked: tuple = (1, 3), dtype=jnp.float32, devices=None) -> dict:
    """Train the plan's first ``len(grids)`` mega-batches of the model of
    ``family`` (its module) on ``data`` (what its ``make_data`` made).

    ``grids[m][r][i]`` holds the sample ids replica ``i`` trained on in
    round ``r`` of mega-batch ``m`` (None: no batch). ``devices`` are the
    cell's chips, over which the ``sharded`` placement splits the replicas
    (default: all of JAX's). Returns each mega-batch's training loss (the
    mean over live rounds of the mean over live replicas), after each
    mega-batch count in ``checked`` the norm of each leaf's change of the
    global model since the start, and each leaf's norm at the start.
    """
    alg = traffic["algorithm"]
    n_rep = 1 if alg == "single" else int(traffic["replicas"])
    b_max = int(traffic["b_max"])
    place = Placement(traffic, n_rep, devices)
    step, merge = place.round(family.loss), place.merge()
    params0 = place.replicated(family.init_params(seed, config, dtype))
    replicas = place.replicas(params0)
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
            batch = place.split(family.pack(data, row, b_max))
            replicas, values = step(replicas, batch,
                                    place.split(jnp.asarray(lr, jnp.float32)),
                                    place.split(jnp.asarray(live)))
            values = np.asarray(values, np.float64)
            round_losses.append(float((values * live).sum() / live.sum()))
        losses.append(float(np.mean(round_losses)))
        if alg == "adaptive":
            norms = np.asarray(_replica_norms(replicas), np.float64) / n_params
            alphas = merge_weights(u, b, norms, traffic["pert_thr"], traffic["delta"])
            merged = merge(replicas, place.split(jnp.asarray(alphas, jnp.float32)),
                           g, gp, place.replicated(jnp.float32(traffic["gamma"])))
            gp, g = g, merged
            # the replicas are freed before their copies of the merge are
            # made, so a chip holds one set at a time
            del replicas
            replicas = place.replicas(merged)
            b, lr = batch_size_scaling(b, lr, u, traffic["b_min"], b_max,
                                       traffic["beta"])
        elif alg == "single":
            g = jax.tree_util.tree_map(lambda x: x[0], replicas)
        else:
            raise ValueError(f"the reference has no algorithm {alg!r}")
        if m in checked:
            deltas[m] = {k_: float(v) for k_, v in _delta_norms(g, params0).items()}
    norms0 = {k_: float(v) for k_, v in _leaf_norms(params0).items()}
    return {"losses": losses, "deltas": deltas, "norms0": norms0}

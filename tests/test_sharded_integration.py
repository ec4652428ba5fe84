"""Sharded-execution integration: run an elastic train round + Algorithm-2
merge on a REAL (2, 2) (data, model) mesh with 4 virtual CPU devices under
``sharding/rules.py::param_specs``, and numerically compare against the
same round and merge on one device. The round (vmapped value-and-grad +
masked ``sgd_update``) and the merge (``normalized_merge`` + broadcast) are
written out here: the trainer places replicas on a 1-D replica mesh only.
Run in a subprocess because the virtual device count must be fixed before
jax initializes.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.configs.archs import ARCHS
    from repro.core import adaptive_sgd as asgd
    from repro.launch import specs as SP
    from repro.optim.sgd import SGDConfig, sgd_update
    from repro.sharding.annotate import sharding_context
    from repro.sharding.rules import (
        MeshAxes, param_specs, to_named, train_batch_specs,
    )
    from repro.models import model as MDL
    from repro.utils import tree as tu

    cfg = ARCHS["llama3.2-1b"].reduced()
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    ax = MeshAxes(cfg, mesh)
    R, B, S = 2, 4, 32

    params = MDL.init(cfg, jax.random.PRNGKey(0))
    replicas = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (R,) + l.shape), params
    )
    batch = SP.make_train_batch(cfg, B, S, seed=1)
    rbatch = {k: jnp.stack([v, v]) for k, v in batch.items()}
    lr = jnp.full((R,), 0.1, jnp.float32)
    mask = jnp.ones((R,), jnp.float32)

    def step(replicas, batch, lr_vec, update_mask):
        grad_fn = jax.value_and_grad(
            lambda p, b: MDL.loss_fn(cfg, p, b), has_aux=True
        )
        (loss, aux), grads = jax.vmap(grad_fn)(replicas, batch)
        new_replicas, _ = sgd_update(
            replicas, grads, lr_vec, SGDConfig(),
            update_mask=update_mask, replica_dim=True,
        )
        return new_replicas, {"loss": loss, "accuracy": aux["accuracy"]}

    def merge(replicas, alphas):
        new_global = asgd.normalized_merge(replicas, alphas, None, None, 0.0)
        return tu.tree_broadcast_replicas(new_global, R)

    # ---- single device reference ----
    ref_replicas, ref_m = jax.jit(step)(replicas, rbatch, lr, mask)
    ref_merged = jax.jit(merge)(ref_replicas, jnp.asarray([0.5, 0.5]))

    # ---- sharded ----
    with sharding_context(mesh, ax.activation_rules()):
        rep_sh = to_named(param_specs(cfg, replicas, mesh, with_replica_dim=True), mesh)
        b_sh = to_named(train_batch_specs(cfg, rbatch, mesh), mesh)
        v_sh = NamedSharding(mesh, P(ax.replica))
        jstep = jax.jit(step, in_shardings=(rep_sh, b_sh, v_sh, v_sh),
                        out_shardings=(rep_sh, None))
        got_replicas, got_m = jstep(
            jax.device_put(replicas, rep_sh), jax.device_put(rbatch, b_sh),
            jax.device_put(lr, v_sh), jax.device_put(mask, v_sh),
        )
        jmerge = jax.jit(merge, in_shardings=(rep_sh, v_sh),
                         out_shardings=rep_sh)
        got_merged = jmerge(got_replicas,
                            jax.device_put(jnp.asarray([0.5, 0.5]), v_sh))

    np.testing.assert_allclose(
        np.asarray(ref_m["loss"]), np.asarray(got_m["loss"]), rtol=2e-3
    )
    for a, b in zip(jax.tree_util.tree_leaves(ref_merged),
                    jax.tree_util.tree_leaves(got_merged)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=3e-2, atol=3e-3,
        )
    print("SHARDED_INTEGRATION_OK devices=", jax.device_count())
""")


@pytest.mark.slow
def test_sharded_train_round_matches_single_device():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_INTEGRATION_OK" in r.stdout

"""End-to-end parity: model forward with Pallas kernels routed in
(interpret mode on CPU) vs the pure-jnp paths. Covers the serving/forward
path (kernels are forward-path drop-ins; training keeps the jnp
paths)."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.archs import ARCHS
from repro.launch import specs as SP
from repro.models import model as MDL


def _prefill_logits(cfg, seed=0):
    params = MDL.init(cfg, jax.random.PRNGKey(seed))
    batch = {
        k: v for k, v in SP.make_train_batch(cfg, 2, 64, seed=seed).items()
        if k in ("tokens", "patch_embeds", "frames")
    }
    return np.asarray(MDL.prefill(cfg, params, batch), np.float32)


@pytest.mark.parametrize(
    "arch,flags",
    [
        ("llama3.2-1b", {"use_flash_kernel": True}),
        ("mamba2-780m", {"use_ssd_kernel": True}),
        ("moonshot-v1-16b-a3b", {"use_gmm_kernel": True}),
        ("jamba-1.5-large-398b",
         {"use_flash_kernel": True, "use_ssd_kernel": True,
          "use_gmm_kernel": True}),
    ],
)
def test_forward_parity_with_kernels(arch, flags):
    base = dataclasses.replace(ARCHS[arch].reduced(), remat=False)
    with_k = dataclasses.replace(base, **flags)
    ref = _prefill_logits(base)
    got = _prefill_logits(with_k)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)

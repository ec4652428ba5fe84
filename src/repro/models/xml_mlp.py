"""The paper's workload: a 3-layer MLP over sparse XML data.

Architecture (identical to the SLIDE testbed the paper adopts): sparse input
layer -> hidden ReLU layer -> softmax output over the (huge) label space,
with cross-entropy loss. The input layer is a sparse-dense matmul
(cuSPARSE SpMM in the paper; our Pallas ``spmm`` kernel on TPU, with the
pure-jnp gather as the fallback on every other backend and the
differential oracle).

Batch layout: padded COO (see data/sparse.py). The ``sample_mask`` makes the
effective batch size adaptive while shapes stay static.

Training runs the **sparse-gradient path** (DESIGN.md §3) by default:
``loss_and_sparse_grad`` splits the loss at the input layer's output, pulls
the head cotangent ``dh`` back with ``jax.vjp``, and emits d``w1`` directly
as a RowSparseGrad — ``vals[b,k] = val[b,k]*mask[b,k] * dh[b]`` on rows
``idx[b,k]`` — so no dense (NF, H) gradient is ever materialized. The dense
autodiff path (``loss_fn`` under ``jax.value_and_grad``) is retained as the
oracle.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.protocol import TrainableModel
from repro.optim.row_sparse import RowSparseGrad


@dataclass(frozen=True)
class XMLMLPConfig:
    n_features: int
    n_classes: int
    hidden: int = 128
    dtype: Any = jnp.float32
    # route the input layer through the Pallas spmm kernel (forward + custom
    # VJP). None = auto: kernel where it lowers natively (TPU), jnp gather
    # elsewhere (interpret-mode Pallas is validated by the kernel tests, not
    # run in training loops).
    use_spmm_kernel: Optional[bool] = None
    sparse_grads: bool = True  # expose the row-sparse d w1 path to the trainer


def _kernel_routed(cfg: XMLMLPConfig) -> bool:
    if cfg.use_spmm_kernel is None:
        return jax.default_backend() == "tpu"
    return cfg.use_spmm_kernel


def stored_rows(n_rows: int, dtype) -> int:
    """``n_rows`` rounded up to ``dtype``'s sublane tile (32 // itemsize:
    8 rows of float32, 16 of bfloat16): the row count w1 is stored with.

    On a TPU the replica-batched w1 scatter is one scatter over the
    flattened (R * rows, H) operand; with ``rows`` a whole number of tiles
    that flatten is a bitcast and the update runs in place on the scan's
    carry; any other count costs three whole-w1 passes a round (DESIGN.md
    §3).
    """
    tile = 32 // jnp.dtype(dtype).itemsize
    return -(-n_rows // tile) * tile


@functools.partial(jax.jit, static_argnums=1)
def _zero_rows_from(w: jax.Array, n: int) -> jax.Array:
    """``w`` with its rows from ``n`` on set to zero."""
    keep = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) < n
    return jnp.where(keep, w, jnp.zeros_like(w))


def init_params(cfg: XMLMLPConfig, rng: jax.Array) -> dict:
    """Paper: weights ~ Normal with std scaled by layer width.

    w1 has ``stored_rows(n_features, dtype)`` rows: the first
    ``n_features`` are the model's, the rest zero. No feature id reaches
    the padding rows, so they are never gathered nor updated.
    """
    k1, k2 = jax.random.split(rng)
    rows = stored_rows(cfg.n_features, cfg.dtype)
    # Drawn at the stored shape and masked, not drawn and padded: with
    # JAX's partitionable threefry an element's draw depends on its index
    # alone, so the first n_features rows equal an unpadded draw bit for
    # bit, and a jitted init fuses the draw into its consumer (a pad stops
    # that and doubles the TPU program's code).
    w1 = jax.random.normal(k1, (rows, cfg.hidden), cfg.dtype)
    w1 = w1 * (1.0 / jnp.sqrt(cfg.n_features))
    if rows > cfg.n_features:
        w1 = _zero_rows_from(w1, cfg.n_features)
    w2 = jax.random.normal(k2, (cfg.hidden, cfg.n_classes), cfg.dtype)
    w2 = w2 * (1.0 / jnp.sqrt(cfg.hidden))
    return {
        "w1": w1,
        "b1": jnp.zeros((cfg.hidden,), cfg.dtype),
        "w2": w2,
        "b2": jnp.zeros((cfg.n_classes,), cfg.dtype),
    }


def _input_layer(cfg: XMLMLPConfig, w1: jax.Array, batch: dict) -> jax.Array:
    """The sparse input layer: h_lin (B, hidden)."""
    with jax.named_scope("input_layer"):
        if _kernel_routed(cfg):
            from repro.kernels.spmm import ops as spmm_ops

            return spmm_ops.spmm(
                batch["feat_idx"], batch["feat_val"], batch["feat_mask"], w1
            )
        return _sparse_input_ref(
            batch["feat_idx"], batch["feat_val"], batch["feat_mask"], w1
        )


def _sparse_input_ref(feat_idx, feat_val, feat_mask, w1):
    """Gather formulation of SpMM: h[b] = sum_k val[b,k] * W1[idx[b,k]]."""
    rows = w1[feat_idx]  # (B, nnz, H)
    scale = (feat_val * feat_mask).astype(w1.dtype)[..., None]
    return jnp.sum(rows * scale, axis=1)


def _head_loss(h_lin: jax.Array, rest: dict, batch: dict):
    """From the input layer's output to (loss, aux).

    Masked multi-label softmax cross-entropy + top-1 accuracy. Loss per
    sample = mean over its true labels of -log p(label); batch loss is
    averaged over *valid* samples only (adaptive batch size).
    """
    with jax.named_scope("head"):
        h = jax.nn.relu(h_lin + rest["b1"])
        logits = (h @ rest["w2"] + rest["b2"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        lab_logp = jnp.take_along_axis(logp, batch["label_idx"], axis=-1)
        lmask = batch["label_mask"].astype(jnp.float32)
        per_sample = -jnp.sum(lab_logp * lmask, axis=-1) / jnp.maximum(
            jnp.sum(lmask, axis=-1), 1.0
        )
        smask = batch["sample_mask"].astype(jnp.float32)
        n_valid = jnp.sum(smask)
        loss = jnp.sum(per_sample * smask) / jnp.maximum(n_valid, 1.0)

        pred = jnp.argmax(logits, axis=-1)
        hit = jnp.any(
            (batch["label_idx"] == pred[:, None]) & batch["label_mask"], axis=-1
        ).astype(jnp.float32)
        acc = jnp.sum(hit * smask) / jnp.maximum(n_valid, 1.0)
        return loss, {"accuracy": acc, "n_valid": n_valid}


def forward(cfg: XMLMLPConfig, params: dict, batch: dict) -> jax.Array:
    """Return logits (B, n_classes)."""
    h = jax.nn.relu(_input_layer(cfg, params["w1"], batch) + params["b1"])
    return h @ params["w2"] + params["b2"]


def loss_fn(cfg: XMLMLPConfig, params: dict, batch: dict):
    """Dense-path loss: differentiate with jax.value_and_grad (the oracle).
    Returns (loss, aux) with aux = dict(accuracy, n_valid)."""
    rest = {k: v for k, v in params.items() if k != "w1"}
    h_lin = _input_layer(cfg, params["w1"], batch)
    return _head_loss(h_lin, rest, batch)


def loss_and_sparse_grad(cfg: XMLMLPConfig, params: dict, batch: dict):
    """Sparse-gradient step math: ((loss, aux), grads) with d w1 row-sparse.

    d w1 flows only through the input layer, whose VJP w.r.t. w1 is
    analytically ``dW[idx[b,k]] += scale[b,k] * dh[b]`` — exactly the
    RowSparseGrad layout, so we pull ``dh`` back through the head with
    jax.vjp and never build the dense (NF, H) gradient. Masked/padded nnz
    slots get the out-of-bounds sentinel row: w1's *stored* row count
    (``stored_rows``), which the scatter drops.
    """
    rest = {k: v for k, v in params.items() if k != "w1"}
    h_lin = _input_layer(cfg, params["w1"], batch)
    loss, head_vjp, aux = jax.vjp(
        lambda h, r: _head_loss(h, r, batch), h_lin, rest, has_aux=True
    )
    dh, drest = head_vjp(jnp.ones_like(loss))

    with jax.named_scope("input_layer"):
        scale = (batch["feat_val"] * batch["feat_mask"]).astype(jnp.float32)
        b, k = scale.shape
        vals = scale[..., None] * dh.astype(jnp.float32)[:, None, :]  # (B, K, H)
        n_rows = params["w1"].shape[0]
        rows = jnp.where(
            batch["feat_mask"], batch["feat_idx"], n_rows
        ).astype(jnp.int32)
        grads = dict(drest)
        grads["w1"] = RowSparseGrad(
            rows.reshape(b * k), vals.reshape(b * k, -1), n_rows
        )
    return (loss, aux), grads


def make_model(cfg: XMLMLPConfig) -> TrainableModel:
    """Bundle (init, loss[, sparse_grad]) as the trainer's TrainableModel."""
    return TrainableModel(
        init=lambda rng: init_params(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch),
        sparse_grad_fn=(
            (lambda params, batch: loss_and_sparse_grad(cfg, params, batch))
            if cfg.sparse_grads else None
        ),
        config=cfg,
    )

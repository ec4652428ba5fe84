"""Public entrypoint for the SpMM kernel (sparse XML input layer).

``spmm`` carries a ``jax.custom_vjp``: the forward is the DMA row-gather
kernel (spmm.py) and the backward is the sorted scatter-add kernel
``spmm_grad_w`` plus the cheap d``feat_val`` gather-dot — both sides of the
paper's "SpMM + its transpose dominate per-update cost" observation run
TPU-native (DESIGN.md §2/§3). ``feat_idx``/``feat_mask`` are integral and
get symbolic-zero (float0) cotangents.

Both kernels keep a weight-sized array in HBM (``pl.ANY``), which Pallas
cannot batch under ``vmap``. They take a leading replica dim instead, and
``custom_vmap`` folds every ``vmap`` axis into it — so the trainer's
``vmap`` over R replicas runs one kernel call with an R grid axis.

Interpret gating: these kernels are built on TPU-specific Mosaic
constructs (SMEM blocks, manual DMA), which the GPU (Triton) lowering does
not implement — so native mode is TPU-only and every other backend runs
interpret mode (kernel bodies still run, so correctness is validated on
every platform / in CI).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .ref import spmm_grad_val_ref
from .spmm import DEFAULT_BLOCK_B, DEFAULT_BLOCK_K, DEFAULT_CHUNK
from .spmm import spmm_grad_w_replicated, spmm_replicated


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def _fold_vmap(replicated):
    """Wrap a replica-batched call (every operand and the result lead with
    R) so that ``vmap`` folds its axis into R instead of batching the
    pallas_call; nested ``vmap``s fold one after another."""

    @jax.custom_batching.custom_vmap
    def call(*args):
        return replicated(*args)

    @call.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [
            a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, batched in zip(args, in_batched)
        ]
        out = call(*[a.reshape((-1,) + a.shape[2:]) for a in args])
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call


@functools.lru_cache(maxsize=None)
def _spmm_call(block_b: int, block_k: int):
    return _fold_vmap(functools.partial(
        spmm_replicated, block_b=block_b, block_k=block_k,
        interpret=_interpret_mode(),
    ))


@functools.lru_cache(maxsize=None)
def _grad_w_call(n_rows: int, chunk: int):
    return _fold_vmap(functools.partial(
        spmm_grad_w_replicated, n_rows=n_rows, chunk=chunk,
        interpret=_interpret_mode(),
    ))


def spmm(feat_idx, feat_val, feat_mask, w, block_b: int = DEFAULT_BLOCK_B,
         block_k: int = DEFAULT_BLOCK_K):
    """Padded-COO batch x dense W. Returns (B, H) in W's dtype. Differentiable
    w.r.t. ``feat_val`` and ``w`` (custom VJP, Pallas both ways).

    One grid step gathers ``block_b`` samples x ``block_k`` nnz slots of W
    rows (DESIGN.md §2)."""
    return _spmm(feat_idx, feat_val, feat_mask, w, int(block_b), int(block_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _spmm(feat_idx, feat_val, feat_mask, w, block_b, block_k):
    out = _spmm_call(block_b, block_k)(
        feat_idx[None], feat_val[None], feat_mask[None], w[None]
    )
    return out[0]


def _spmm_fwd(feat_idx, feat_val, feat_mask, w, block_b, block_k):
    out = _spmm(feat_idx, feat_val, feat_mask, w, block_b, block_k)
    return out, (feat_idx, feat_val, feat_mask, w)


def _spmm_bwd(block_b, block_k, res, dh):
    feat_idx, feat_val, feat_mask, w = res
    dw = spmm_grad_w(
        feat_idx, feat_val, feat_mask, dh, w.shape[0]
    ).astype(w.dtype)
    # d feat_val: gather-dot, same O(B*K*H) footprint as the forward
    dval = spmm_grad_val_ref(feat_idx, feat_mask, w, dh).astype(feat_val.dtype)
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)  # integral primals
    return f0(feat_idx), dval, f0(feat_mask), dw


_spmm.defvjp(_spmm_fwd, _spmm_bwd)


def spmm_grad_w(feat_idx, feat_val, feat_mask, dh, n_rows: int,
                chunk: int = DEFAULT_CHUNK):
    """Standalone transpose-SpMM: scatter-add ``scale[b,k] * dh[b]`` into the
    gathered rows. ``chunk`` = sorted slots per grid step. Returns
    (n_rows, H) f32."""
    out = _grad_w_call(int(n_rows), int(chunk))(
        feat_idx[None], feat_val[None], feat_mask[None], dh[None]
    )
    return out[0]

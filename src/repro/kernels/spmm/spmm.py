"""Pallas TPU kernels: padded-COO batch SpMM (the paper's sparse input layer)
and its transpose.

GPU algorithm (cuSPARSE CSR SpMM) does not transfer to TPU: there is no
sparse unit, and warp-level row decomposition has no analogue. The
TPU-native formulation (DESIGN.md §2) is a **manual DMA row gather + MXU
accumulate**:

  * W stays in HBM (``memory_space=pl.ANY``). Each grid step covers
    ``block_b`` samples x ``block_k`` nnz slots: it reads the slot indices
    from an SMEM block, starts one HBM->VMEM DMA per slot into a
    ``(block_b*block_k, H)`` VMEM scratch (all on one DMA semaphore), waits
    for them, and accumulates ``S @ rows`` into the ``(block_b, H)`` f32
    output tile, where ``S`` is the block-diagonal ``(block_b,
    block_b*block_k)`` matrix of ``val*mask`` built outside the kernel.
  * Every operand is laid out so a block's last two dims equal the array's
    (``(steps, 1, n)`` indices, ``(steps, block_b, n)`` scales,
    ``(R, B/block_b, block_b, H)`` output) — the Mosaic tiling rule holds
    for any block size, and no one-row block ever reaches the compiler.
  * The output tile's index ignores the K axis, so the accumulator stays in
    VMEM across the whole reduction; only W rows move.

Both kernels take a leading replica dim R as a grid axis: Pallas cannot
batch an HBM (``pl.ANY``) operand under ``vmap``, so the public entry
points (``ops.py``) route ``vmap`` to these replica-batched calls.

Zero-padding slots contribute 0 via the scale; their index is clipped into
range, so every DMA reads a real row. B and K are padded up to block
multiples with zero-scale slots.

The **backward** (DESIGN.md §3) is the transpose: ``spmm_grad_w`` is a
scatter-add of ``scale[b,k] * dh[b]`` into the gathered rows. Write
conflicts (the same row touched by many (b, k) slots) are handled by
sorting each replica's slots by row id first, so all updates to one row are
consecutive: a sequential loop accumulates the run of one row in VMEM and,
when the row changes, DMAs the finished sum to that row of the HBM output
exactly once. Rows never touched keep the zeros of the aliased initializer
(``input_output_aliases``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_B = 8
DEFAULT_BLOCK_K = 128
DEFAULT_CHUNK = 512


def _gather_kernel(idx_ref, s_ref, w_hbm, out_ref, rows, sem):
    """Grid (R, B/bb, K/bk). idx_ref (1, 1, n) SMEM slot rows; s_ref
    (1, bb, n) block-diagonal scales; w_hbm (R, NF, H) in HBM; out_ref
    (1, 1, bb, H) f32 accumulator; rows (n, H) VMEM gather buffer."""
    r, ki = pl.program_id(0), pl.program_id(2)
    n = rows.shape[0]

    @pl.when(ki == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def copy(j, row):
        return pltpu.make_async_copy(
            w_hbm.at[r, pl.ds(row, 1)], rows.at[pl.ds(j, 1)], sem
        )

    def start(j, carry):
        copy(j, idx_ref[0, 0, j]).start()
        return carry

    def wait(j, carry):
        copy(j, 0).wait()  # same byte count as every started copy
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    out_ref[0, 0] += jnp.dot(
        s_ref[0], rows[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_k", "interpret")
)
def spmm_replicated(
    feat_idx: jax.Array,    # (R, B, K) int
    feat_val: jax.Array,    # (R, B, K) float
    feat_mask: jax.Array,   # (R, B, K) bool
    w: jax.Array,           # (R, NF, H)
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Per replica r: out[r, b] = sum_k val*mask[r,b,k] * w[r, idx[r,b,k]].
    Returns (R, B, H) in W's dtype."""
    n_rep, b, k = feat_idx.shape
    nf, h = w.shape[1:]
    bb, bk = max(1, min(block_b, b)), max(1, min(block_k, k))
    pad_b, pad_k = (-b) % bb, (-k) % bk
    idx = jnp.clip(feat_idx.astype(jnp.int32), 0, nf - 1)
    scale = (feat_val * feat_mask).astype(jnp.float32)
    if pad_b or pad_k:  # zero-scale slots gathering row 0
        pad = ((0, 0), (0, pad_b), (0, pad_k))
        idx, scale = jnp.pad(idx, pad), jnp.pad(scale, pad)
    nb, nk, n = (b + pad_b) // bb, (k + pad_k) // bk, bb * bk

    def blocked(x):  # (R, B, K) -> (R, nb, nk, bb, bk)
        return x.reshape(n_rep, nb, bb, nk, bk).transpose(0, 1, 3, 2, 4)

    steps = n_rep * nb * nk
    idx_l = blocked(idx).reshape(steps, 1, n)
    eye = jnp.eye(bb, dtype=jnp.float32)
    s_bd = (eye[:, :, None] * blocked(scale)[..., None, :, :]).reshape(
        steps, bb, n
    )

    def step(ri, bi, ki):
        return (ri * nb + bi) * nk + ki

    out = pl.pallas_call(
        _gather_kernel,
        grid=(n_rep, nb, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, n), lambda ri, bi, ki: (step(ri, bi, ki), 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((1, bb, n), lambda ri, bi, ki: (step(ri, bi, ki), 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # W: rows are DMA'd by hand
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bb, h), lambda ri, bi, ki: (ri, bi, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((n_rep, nb, bb, h), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, h), w.dtype), pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(idx_l, s_bd, w)
    return out.reshape(n_rep, nb * bb, h)[:, :b].astype(w.dtype)


# --------------------------------------------------------------------------
# backward: dW scatter-add (sorted formulation, DESIGN.md §3)
# --------------------------------------------------------------------------


def _grad_w_kernel(rows_ref, samp_ref, scale_ref, dh_ref, init_hbm, out_hbm,
                   acc, cur, stage, sem):
    """Grid (R, S/chunk), sequential. rows/samp/scale (1, 1, chunk) SMEM are
    this chunk's row-sorted slots; dh_ref (1, B, H) VMEM. ``acc`` (1, H)
    holds the running sum of row ``cur[0]`` across chunks; each finished
    run is copied into its own ``stage`` row and DMA'd to ``out_hbm`` (the
    aliased zero initializer) once."""
    del init_hbm  # aliased to out_hbm: only its zeros for untouched rows matter
    r, c = pl.program_id(0), pl.program_id(1)
    chunk = rows_ref.shape[-1]

    @pl.when(c == 0)
    def _start_replica():
        cur[0] = -1
        acc[...] = jnp.zeros_like(acc)

    def flush(n_out):
        stage[pl.ds(n_out, 1), :] = acc[...]
        pltpu.make_async_copy(
            stage.at[pl.ds(n_out, 1)], out_hbm.at[r, pl.ds(cur[0], 1)], sem
        ).start()

    def body(j, n_out):
        row = rows_ref[0, 0, j]
        changed = row != cur[0]
        done = changed & (cur[0] >= 0)

        @pl.when(done)
        def _():
            flush(n_out)

        @pl.when(changed)
        def _():
            cur[0] = row
            acc[...] = jnp.zeros_like(acc)

        acc[...] += scale_ref[0, 0, j] * dh_ref[0, pl.ds(samp_ref[0, 0, j], 1), :]
        return n_out + done.astype(jnp.int32)

    n_out = jax.lax.fori_loop(0, chunk, body, jnp.int32(0))
    last = c == pl.num_programs(1) - 1

    @pl.when(last)
    def _():
        flush(n_out)

    def wait(j, carry):
        pltpu.make_async_copy(
            stage.at[pl.ds(j, 1)], out_hbm.at[r, pl.ds(0, 1)], sem
        ).wait()
        return carry

    jax.lax.fori_loop(0, n_out + last.astype(jnp.int32), wait, 0)


@functools.partial(
    jax.jit, static_argnames=("n_rows", "chunk", "interpret")
)
def spmm_grad_w_replicated(
    feat_idx: jax.Array,    # (R, B, K) int
    feat_val: jax.Array,    # (R, B, K) float
    feat_mask: jax.Array,   # (R, B, K) bool
    dh: jax.Array,          # (R, B, H) cotangent of the spmm output
    n_rows: int,            # NF
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """dW[r, i] = sum_{(b,k): idx[r,b,k]=i} val*mask[r,b,k] * dh[r,b].
    Returns (R, NF, H) f32. Sorting each replica's S = B*K slots by row id
    makes duplicate-row updates consecutive (write-conflict handling);
    zero-scale (masked / padded) slots add 0 to whatever row they point at,
    so no sentinel is needed and every index stays in range."""
    n_rep, b, k = feat_idx.shape
    h = dh.shape[-1]
    s = b * k
    flat = jnp.clip(feat_idx.reshape(n_rep, s).astype(jnp.int32), 0, n_rows - 1)
    order = jnp.argsort(flat, axis=-1)
    rows_s = jnp.take_along_axis(flat, order, axis=-1)
    samp_s = (order // k).astype(jnp.int32)
    scale = (feat_val * feat_mask).astype(jnp.float32).reshape(n_rep, s)
    scale_s = jnp.take_along_axis(scale, order, axis=-1)

    chunk = max(1, min(chunk, s))
    pad = (-s) % chunk
    if pad:  # zero-scale slots extending the last run
        rows_s = jnp.pad(rows_s, ((0, 0), (0, pad)), mode="edge")
        samp_s = jnp.pad(samp_s, ((0, 0), (0, pad)))
        scale_s = jnp.pad(scale_s, ((0, 0), (0, pad)))
    n_chunks = (s + pad) // chunk

    def chunked(x):  # (R, S) -> (R * n_chunks, 1, chunk)
        return x.reshape(n_rep * n_chunks, 1, chunk)

    slot_spec = pl.BlockSpec(
        (1, 1, chunk), lambda ri, ci: (ri * n_chunks + ci, 0, 0),
        memory_space=pltpu.SMEM,
    )
    init = jnp.zeros((n_rep, n_rows, h), jnp.float32)
    return pl.pallas_call(
        _grad_w_kernel,
        grid=(n_rep, n_chunks),
        in_specs=[
            slot_spec, slot_spec, slot_spec,
            pl.BlockSpec((1, b, h), lambda ri, ci: (ri, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_rep, n_rows, h), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((chunk + 1, h), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
        input_output_aliases={4: 0},  # init (input 4) is the output buffer
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(chunked(rows_s), chunked(samp_s), chunked(scale_s),
      dh.astype(jnp.float32), init)

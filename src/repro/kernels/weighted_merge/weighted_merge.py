"""Pallas TPU kernel for Algorithm 2's weighted model merge.

``out = sum_r alphas[r] * replicas[r] (+ gamma * (g - gp))``

This is the per-shard compute of the paper's multi-stream all-reduce model
merging (§4): on TPU the cross-device reduction is a psum generated by GSPMD;
this kernel is the *local* fused weighted-combine that replaces the paper's
CUDA "fused elementwise" kernels. Fusing the R-way scale+add with the
momentum term reads every replica shard exactly once from HBM (the op is
purely memory-bound, so minimizing bytes moved is the whole game).

Layout: each parameter leaf is merged in the layout it is stored in.
``order`` is the leaf's major-to-minor dimension order on the device (the
backend's default layout for its shape, ``ops.stored_order``). The leaf is
transposed to that order, which the compiler turns into a bitcast, so the
kernel reads the bytes as they lie in HBM: nothing is flattened, padded or
relaid out. The chip tiles the last two dimensions of that view; the
merged leaf keeps the same order without the replica axis, and its last
two dimensions (one for a bias) are cut into blocks. The replica axis sits
either among the leading, untiled dimensions (w1, ``(R, rows, H)``) or on
the sublanes (w2, ``(R, H, C)`` stored as ``(C, R, H)``; a bias ``(R, C)``).
Each grid step reads the replicas' block with the whole replica axis, and
the kernel takes replica r as a slice of it. A leaf stored with the replica
axis on the lanes, and a 16-bit vector a replica, are flattened to ``(R, 1,
N)`` first (a copy; ``ops.flat_leaves`` counts them).

Tiles come from the leaf's shape and dtype: a block of the merged leaf
holds up to 1 MiB, and one grid step's blocks (R of the replicas, padded
to 16 on the sublanes for 16-bit data, g's, gp's and the output's) up to
4 MiB, so the double-buffered pipeline stays near 8 MiB, half the v5e's
default scoped VMEM. Block edges are multiples of the (8, 128) tile (16
rows for 16-bit dtypes) or a whole dimension; a ``cdiv`` grid lets Pallas
mask the ragged last block.

Arithmetic: float32 accumulation whatever the storage dtype, replicas added
in the order r = 0..R-1, then ``gamma * (g - gp)`` added to the sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_BYTES = 1 << 20  # largest block of the merged leaf
STEP_BYTES = 4 << 20   # one grid step's blocks; double-buffered: 8 MiB


def _round_down(x: int, m: int) -> int:
    return max(m, x // m * m)


def _sublanes(dtype) -> int:
    """Rows of the chip's (rows, 128) tile: 8 for 32-bit, 16 for 16-bit."""
    return 32 // jnp.dtype(dtype).itemsize


def _block(q, dtype, n_blocks: int):
    """Block of the merged leaf's last dims ``q[-2:]`` (``q[-1:]`` for a
    1-D leaf), when a grid step moves ``n_blocks`` blocks of its size."""
    sub = _sublanes(dtype)
    elems = min(BLOCK_BYTES, STEP_BYTES // n_blocks) // jnp.dtype(dtype).itemsize
    cols = q[-1]
    if len(q) == 1:
        return (cols if cols <= elems else _round_down(elems, sub * LANES),)
    rows = q[-2]
    first = min(rows, sub)
    if cols * first > elems:
        return first, _round_down(elems // first, LANES)
    br = _round_down(elems // cols, sub)
    return (rows if br >= rows else br), cols


def _kernel(alpha_ref, rep_ref, *refs, at, gamma):
    """out = sum_r alpha[r] * rep[r] (+ gamma * (g - gp)); replica r is
    ``rep_ref`` indexed at block axis ``at``."""
    out_ref = refs[-1]
    lead = (slice(None),) * at
    acc = alpha_ref[0] * rep_ref[lead + (0,)].astype(jnp.float32)
    for r in range(1, rep_ref.shape[at]):
        acc = acc + alpha_ref[r] * rep_ref[lead + (r,)].astype(jnp.float32)
    if len(refs) == 3:
        g_ref, gp_ref = refs[:2]
        acc = acc + gamma * (
            g_ref[...].astype(jnp.float32) - gp_ref[...].astype(jnp.float32)
        )
    out_ref[...] = acc.astype(out_ref.dtype)


def _inverse(perm):
    return tuple(sorted(range(len(perm)), key=perm.__getitem__))


def _transpose(x, perm):
    return x if tuple(perm) == tuple(range(x.ndim)) else jnp.transpose(x, perm)


def merged_order(order) -> tuple:
    """The merged leaf's dimension order that ``order`` (a replica-stacked
    leaf's) leaves when its replica axis is dropped."""
    return tuple(d - 1 for d in order if d != 0)


def reads_as_stored(shape, order, dtype) -> bool:
    """Whether a replica-stacked leaf stored in ``order`` is merged in
    place. Not when its replica axis lies on the lanes (or it holds one
    scalar a replica), nor for a 16-bit vector a replica: the chip packs two
    of its rows into one sublane, and Mosaic cannot take one row out."""
    if len(shape) < 2 or order[-1] == 0:
        return False
    return len(shape) > 2 or jnp.dtype(dtype).itemsize >= 4


@functools.partial(
    jax.jit, static_argnames=("order", "gamma", "interpret")
)
def weighted_merge(
    replicas: jax.Array,           # (R, *leaf)
    alphas: jax.Array,             # (R,)
    g: jax.Array | None = None,    # leaf
    gp: jax.Array | None = None,   # leaf
    gamma: float = 0.0,
    *,
    order: tuple | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Merged leaf, shaped like ``replicas[0]``. ``order`` is the replicas'
    major-to-minor dimension order on the device (None: row-major)."""
    shape, r = replicas.shape, replicas.shape[0]
    order = tuple(range(len(shape))) if order is None else tuple(order)
    use_momentum = g is not None and gamma != 0.0
    if not reads_as_stored(shape, order, replicas.dtype):
        # one row of every leading block: (R, 1, N) merged to (1, N)
        replicas = replicas.reshape(r, 1, -1)
        if use_momentum:
            g, gp = g.reshape(1, -1), gp.reshape(1, -1)
        out = weighted_merge(replicas, alphas, g, gp, gamma,
                             interpret=interpret)
        return out.reshape(shape[1:])

    p = tuple(shape[d] for d in order)   # the replicas as stored
    at = order.index(0)                  # where the replica axis lies
    q = p[:at] + p[at + 1:]              # the merged leaf as stored
    lead = max(len(q) - 2, 0)            # its untiled dims: one index a step
    rep_blocks = r
    if at >= len(p) - 2 and replicas.dtype.itemsize < 4:
        # on the sublanes, VMEM pads a 16-bit replica block to its tile
        sub = _sublanes(replicas.dtype)
        rep_blocks = -(-r // sub) * sub
    block = _block(q, replicas.dtype, rep_blocks + (3 if use_momentum else 1))
    out_block = (pl.squeezed,) * lead + block
    rep_block = out_block[:at] + (r,) + out_block[at:]
    grid = q[:lead] + tuple(pl.cdiv(n, b) for n, b in zip(q[lead:], block))

    def rep_index(*i):
        return i[:at] + (0,) + i[at:]

    out_spec = pl.BlockSpec(out_block, lambda *i: i)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(rep_block, rep_index)]
    operands = [alphas.astype(jnp.float32), _transpose(replicas, order)]
    q_order = merged_order(order)
    if use_momentum:
        in_specs += [out_spec, out_spec]
        operands += [_transpose(g, q_order), _transpose(gp, q_order)]
    out = pl.pallas_call(
        # the replica axis's place in the block once squeezed dims are gone
        functools.partial(_kernel, at=max(0, at - lead), gamma=gamma),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(q, replicas.dtype),
        interpret=interpret,
        name="weighted_merge",
    )(*operands)
    return _transpose(out, _inverse(q_order))

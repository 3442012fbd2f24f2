"""Fused gossip aggregation kernel (paper Eq. 5): the DFL mixing hot-spot.

    y = x + sum_k w_k * (u_k - x)

over K stacked neighbor buffers. Unfused this is K+1 HBM round trips of
the full parameter vector; fused it is ONE read of x, one streamed read
of each u_k block, one write — memory-bound, so the fusion is the whole
win. Blocks are (8, 1024) f32 tiles (VPU-aligned: 8 sublanes x 128 lanes
x 8). Inputs whose shape is not a tile multiple are zero-padded to the
block grid internally and the output sliced back, so real model sizes
(P any value, not just multiples of 8192) go through the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8
BLOCK_COLS = 1024
ROWS_BLOCK_COLS = 512   # gossip_mix_rows: all W rows of a column tile resident


def _gossip_kernel(w_ref, x_ref, u_ref, o_ref, *, num_neighbors: int):
    x = x_ref[...].astype(jnp.float32)                    # [R, C]
    acc = x
    for kidx in range(num_neighbors):                     # K is small/static
        w = w_ref[kidx, 0]
        acc = acc + w * (u_ref[kidx].astype(jnp.float32) - x)
    o_ref[...] = acc.astype(o_ref.dtype)


def pad_to_blocks(r: int, c: int, block_rows: int = BLOCK_ROWS,
                  block_cols: int = BLOCK_COLS) -> tuple[int, int, int, int]:
    """Block shape + padded extent for an [R, C] operand: blocks never
    exceed the array, and the array is padded up to a whole block grid.
    Callers with their own tile constants pass them explicitly."""
    br, bc = min(block_rows, r), min(block_cols, c)
    rp = -(-r // br) * br
    cp = -(-c // bc) * bc
    return br, bc, rp, cp


def gossip_mix_2d(x, u, w, *, interpret: bool = False):
    """x: [R, C]; u: [K, R, C] neighbor buffers; w: [K] f32 weights.

    R and C need not be tile multiples: the padding shim zero-extends to
    the block grid and slices the result back (padding rows mix to zero,
    which is discarded)."""
    r, c = x.shape
    k = u.shape[0]
    br, bc, rp, cp = pad_to_blocks(r, c)
    if (rp, cp) != (r, c):
        x = jnp.pad(x, ((0, rp - r), (0, cp - c)))
        u = jnp.pad(u, ((0, 0), (0, rp - r), (0, cp - c)))
    kernel = functools.partial(_gossip_kernel, num_neighbors=k)
    out = pl.pallas_call(
        kernel,
        grid=(rp // br, cp // bc),
        in_specs=[
            pl.BlockSpec((k, 1), lambda i, j: (0, 0)),     # weights: whole
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((k, br, bc), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), x.dtype),
        interpret=interpret,
    )(w.reshape(k, 1).astype(jnp.float32), x, u)
    if (rp, cp) != (r, c):
        out = out[:r, :c]
    return out


def _rows_kernel(m_ref, x_ref, o_ref, *, num_workers: int):
    x = x_ref[...].astype(jnp.float32)                    # [W, bc]
    acc = x
    for k in range(num_workers):                          # W is small/static
        acc = acc + m_ref[:, k:k + 1] * (x[k:k + 1, :] - x)
    o_ref[...] = acc.astype(o_ref.dtype)


def gossip_mix_rows(x, mix, *, interpret: bool = False):
    """x: [W, C] worker rows; mix: [W, W] mixing matrix.

    Returns y with ``y_i = x_i + sum_k mix[i, k] (x_k - x_i)``, summed
    over k in ascending order: per element the arithmetic of
    ``gossip_mix_2d`` vmapped over the rows of ``mix``, but on the [W, C]
    row layout the engines hold, so no [W, rows, 1024] relayout (and no
    padded copy) feeds it. One program per column tile with every worker
    row resident (the ``gossip_edges`` layout); a ragged last tile is
    masked by Pallas, and columns never mix with each other."""
    r, c = x.shape
    bc = min(ROWS_BLOCK_COLS, c)
    kernel = functools.partial(_rows_kernel, num_workers=r)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(c, bc),),
        in_specs=[
            pl.BlockSpec((r, r), lambda j: (0, 0)),          # mix: whole
            pl.BlockSpec((r, bc), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((r, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        interpret=interpret,
    )(mix.astype(jnp.float32), x)

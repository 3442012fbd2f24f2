"""Fused gossip aggregation kernel (paper Eq. 5): the DFL mixing hot-spot.

    y = x + sum_k w_k * (u_k - x)

over K stacked neighbor buffers. Unfused this is K+1 HBM round trips of
the full parameter vector; fused it is ONE read of x, one streamed read
of each u_k block, one write — memory-bound, so the fusion is the whole
win. Blocks are (8, 1024) f32 tiles (VPU-aligned: 8 sublanes x 128 lanes
x 8). Inputs whose shape is not a tile multiple are zero-padded to the
block grid internally and the output sliced back, so real model sizes
(P any value, not just multiples of 8192) go through the kernel.

``gossip_mix_rows`` mixes the fused engines' [W, P] rows as they lie,
all W rows of a column tile resident. Its tile width, shared with
``kernels/gossip_edges.py``, comes from the row count
(``row_tile_cols``): the widest multiple of 128 columns whose
double-buffered input and output blocks fit ``ROW_TILE_VMEM``, so a
small fleet takes tens of thousands of columns a grid step and the
step's fixed cost is amortised, while W=2048 still fits at 256. The
body walks the tile in register-sized lane chunks (``lane_chunks``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8
BLOCK_COLS = 1024
# Row-layout kernels (gossip_mix_rows, gossip_edges): VMEM the pipelined
# blocks of one column tile may hold, half of the v5e's 16 MiB default
# scoped VMEM, so the body's own values fit beside them
ROW_TILE_VMEM = 8 * 1024 * 1024
# ... and the bytes of one [rows, chunk] value the body computes on at a
# time: 16 vregs, so a few such values stay in the 64 vector registers
ROW_CHUNK_BYTES = 16 * 4096
LANES = 128


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


def _padded_rows(rows: int, itemsize: int) -> int:
    """Rows as VMEM holds them: a multiple of one (8, 128) tile's rows
    (8 for 32-bit, 16 for 16-bit elements)."""
    pack = 8 * max(1, 4 // itemsize)
    return -(-rows // pack) * pack


def row_tile_cols(rows: int, cols: int, itemsize: int) -> int:
    """Column tile of a kernel that keeps all ``rows`` of an [rows, cols]
    operand resident: the widest multiple of 128 columns whose pipelined
    blocks (one input and one output block, each double-buffered, rows
    padded to whole sublane tiles) fit ``ROW_TILE_VMEM``, never wider
    than the array. At 4 bytes: 65,536 columns for W=4, 32,768 for
    W=12, 16,384 for W=30, 256 for W=2048."""
    per_col = 2 * 2 * _padded_rows(rows, itemsize) * itemsize
    bc = max(LANES, ROW_TILE_VMEM // per_col // LANES * LANES)
    return min(bc, cols)


def lane_chunks(width: int, rows: int, itemsize: int, body, *,
                unroll: bool = False) -> None:
    """Run ``body(cols)`` over lane chunks of a resident block ``width``
    columns wide, each chunk whole 128-lane tiles and about
    ``ROW_CHUNK_BYTES`` at ``rows`` rows, so no value the kernel computes
    on is the whole block: a ``fori_loop`` over the full chunks (fully
    unrolled with ``unroll``), then the tail once."""
    per_lane = _padded_rows(rows, itemsize) * itemsize
    chunk = max(LANES, ROW_CHUNK_BYTES // per_lane // LANES * LANES)
    n, tail = divmod(width, chunk)
    if n:
        def step(i, carry):
            body(pl.ds(pl.multiple_of(i * chunk, chunk), chunk))
            return carry
        jax.lax.fori_loop(0, n, step, 0, unroll=unroll)
    if tail:
        body(pl.ds(n * chunk, tail))


def _rows_kernel(m_ref, x_ref, o_ref, *, num_workers: int):
    m = m_ref[...]

    def chunk(cols):
        x = x_ref[:, cols].astype(jnp.float32)            # [W, chunk]
        acc = x
        for k in range(num_workers):                      # W is small/static
            acc = acc + m[:, k:k + 1] * (x[k:k + 1, :] - x)
        o_ref[:, cols] = acc.astype(o_ref.dtype)

    lane_chunks(x_ref.shape[1], num_workers, x_ref.dtype.itemsize, chunk)


def gossip_mix_rows(x, mix, *, interpret: bool = False):
    """x: [W, C] worker rows; mix: [W, W] mixing matrix.

    Returns y with ``y_i = x_i + sum_k mix[i, k] (x_k - x_i)``, summed
    over k in ascending order: per element the arithmetic of
    ``gossip_mix_2d`` vmapped over the rows of ``mix``, but on the [W, C]
    row layout the engines hold, so no [W, rows, 1024] relayout (and no
    padded copy) feeds it. One program per column tile with every worker
    row resident (the ``gossip_edges`` layout), the tile as wide as
    ``row_tile_cols`` allows for W rows, its body run over lane chunks
    (``lane_chunks``); a ragged last tile is masked by Pallas, and
    columns never mix with each other."""
    r, c = x.shape
    bc = row_tile_cols(r, c, x.dtype.itemsize)
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

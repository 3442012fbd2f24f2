"""Mask-and-pack sparsification kernel for top-k / rand-k gossip.

Used by the sparse wire codecs (``cfg.compress == "topk:<k>"`` /
``"randk:<k>"``, ChocoSGD-style with error feedback):
``core/compression.py`` computes the per-worker keep threshold (the k-th
largest gate value — |z| for top-k, a seeded uniform score for rand-k)
and this kernel applies it on the engines' [rows, cols] wire layout in
one HBM pass: values at kept coordinates pass through untouched, the
rest are zeroed, and a per-tile survivor count is emitted (the "pack"
accounting the wire-bits model charges — k values plus explicit indices
for top-k, k values plus the shared mask seed for rand-k).

Because the kernel is a pure select (no rounding), its output is
bit-identical to the jnp oracle (``kernels/ref.sparsify_block_ref``) —
the fused engines route through the kernel, the reference engines
through the oracle, and the differential harness holds exactly as it
does for the int8 quantize kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gossip_mix import pad_to_blocks
from repro.kernels.quantize_block import LANES, lane_row_spec

BLOCK_ROWS = 8
BLOCK_COLS = 1024


def _sparsify_kernel(t_ref, x_ref, g_ref, y_ref, n_ref):
    x = x_ref[...]
    keep = g_ref[...].astype(jnp.float32) >= t_ref[0, 0]
    y_ref[...] = jnp.where(keep, x, jnp.zeros_like(x)).astype(y_ref.dtype)
    n_ref[...] = jnp.full(n_ref.shape, jnp.sum(keep.astype(jnp.int32)),
                          jnp.int32)


def sparsify_block_2d(x, gate, thresh, *, interpret: bool = False):
    """x, gate: [R, C]; thresh: scalar keep threshold on ``gate``.

    Returns (y [R, C], nnz i32 [ceil(R/BR), ceil(C/BC)]): y keeps x where
    ``gate >= thresh`` and is zero elsewhere; nnz counts the survivors
    per (8, 1024) tile. Non-tile-multiple shapes are padded to the block
    grid — x with zeros, gate with -1 so padding never survives the
    threshold (keeping the nnz accounting exact) — and y is sliced back.
    """
    r, c = x.shape
    assert gate.shape == (r, c), (x.shape, gate.shape)
    br, bc, rp, cp = pad_to_blocks(r, c, BLOCK_ROWS, BLOCK_COLS)
    if (rp, cp) != (r, c):
        x = jnp.pad(x, ((0, rp - r), (0, cp - c)))
        gate = jnp.pad(gate, ((0, rp - r), (0, cp - c)),
                       constant_values=-1.0)
    nr, nc = rp // br, cp // bc
    y, nnz = pl.pallas_call(
        _sparsify_kernel,
        grid=(nr, nc),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),     # thresh: whole
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                   lane_row_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), x.dtype),
            jax.ShapeDtypeStruct((nr, nc, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(thresh, jnp.float32).reshape(1, 1), x, gate)
    if (rp, cp) != (r, c):
        y = y[:r, :c]
    return y, nnz[:, :, 0, 0]

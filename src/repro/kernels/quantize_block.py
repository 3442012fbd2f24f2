"""Per-block int8 quantize/dequantize kernel with f32 scales.

Used by the compressed-gossip path (beyond-paper optimization, ChocoSGD/
DeepSqueeze-style): ``core/compression.py`` defines the wire format and
the error-feedback compensated update, ``core/fused.py`` runs these
kernels on the flattened [W, P] parameter matrix inside its round scan
(``cfg.compress == "int8"``), and ``runtime/collectives.
gossip_compressed_fn`` ships the same format over ``lax.ppermute``. The
payload each neighbor receives shrinks ~4x (f32) on the wire, with error
feedback keeping the mixing bias compensated. Scales are per (8, 1024)
tile — fine enough to track gossip dynamic range, coarse enough that the
scale side-channel is ~0.05% of payload.

Pallas TPU blocks must be (8, 128)-aligned in their last two dims (or
span the whole array), so a tile's scale cannot be a (1, 1) block of
the [nr, nc] scale grid. Each grid step instead writes its scale across
one lane row of an [nr, nc, 1, 128] buffer (block ``(1, 128)``), and the
wrappers read lane 0 back; values are unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gossip_mix import pad_to_blocks

BLOCK_ROWS = 8
BLOCK_COLS = 1024
QMAX = 127.0
LANES = 128             # one lane row carries a tile's scale


def lane_row_spec():
    """BlockSpec of a per-tile scalar held as one (1, LANES) lane row of
    an [nr, nc, 1, LANES] buffer — the TPU-legal form of a (1, 1) block
    of an [nr, nc] grid (shared with ``sparsify_block``'s counts)."""
    return pl.BlockSpec((None, None, 1, LANES), lambda i, j: (i, j, 0, 0))


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax / QMAX, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.full(s_ref.shape, scale, jnp.float32)


def quantize_block_2d(x, *, interpret: bool = False):
    """x: [R, C] -> (q int8 [R, C], scales f32 [ceil(R/BR), ceil(C/BC)]).

    Non-tile-multiple shapes are zero-padded to the block grid (zeros
    never raise a tile's amax, so scales are unaffected) and q is sliced
    back to the input shape."""
    r, c = x.shape
    br, bc, rp, cp = pad_to_blocks(r, c, BLOCK_ROWS, BLOCK_COLS)
    if (rp, cp) != (r, c):
        x = jnp.pad(x, ((0, rp - r), (0, cp - c)))
    nr, nc = rp // br, cp // bc
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nr, nc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                   lane_row_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), jnp.int8),
            jax.ShapeDtypeStruct((nr, nc, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    if (rp, cp) != (r, c):
        q = q[:r, :c]
    return q, s[:, :, 0, 0]


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[0, 0]) \
        .astype(x_ref.dtype)


def dequantize_block_2d(q, scales, dtype=jnp.float32, *,
                        interpret: bool = False):
    """Inverse of ``quantize_block_2d`` (same padding shim: recomputes the
    block shape ``quantize_block_2d`` used from q's shape)."""
    r, c = q.shape
    nr, nc = scales.shape
    br, bc, rp, cp = pad_to_blocks(r, c, BLOCK_ROWS, BLOCK_COLS)
    assert (nr, nc) == (rp // br, cp // bc), (q.shape, scales.shape)
    if (rp, cp) != (r, c):
        q = jnp.pad(q, ((0, rp - r), (0, cp - c)))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(nr, nc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j)),
                  lane_row_spec()],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), dtype),
        interpret=interpret,
    )(q, jnp.broadcast_to(scales[:, :, None, None], (nr, nc, 1, LANES)))
    if (rp, cp) != (r, c):
        x = x[:r, :c]
    return x

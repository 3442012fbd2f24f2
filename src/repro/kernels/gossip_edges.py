"""Sparse edge-list gossip kernel: gather-mix-scatter on [W, C].

    y = x;  for each directed edge e:  y[dst_e] += w_e * (x[src_e] - x[dst_e])

the O(E·C) sparse form of the dense mixing ``W @ X`` (Eq. 5) — the dense
form is O(W²·C) compute and needs a [W, W] matrix per round, which is
the wall this kernel removes. Every delta reads the PRE-mix ``x`` for
both endpoints, so the result is exactly ``x + Σ_e w_e (x_src - x_dst)``
scattered onto rows, i.e. the off-diagonal part of the row-stochastic
mixing matrix; self-weights are implicit.

Grid: one program per column tile of ``x`` as it lies — each program
keeps all W rows of its tile resident, the tile as wide as
``gossip_mix.row_tile_cols`` allows for W rows (the pipelined input and
output blocks fill a fixed VMEM budget: 32,768 columns at W=12, 256 at
W=2048), and Pallas masks the ragged last tile, so ``x`` is neither
padded nor sliced. The program walks the whole edge list with a
``fori_loop`` of dynamic row gathers/scatters (``pl.ds``), each edge
over the tile's lane chunks (``gossip_mix.lane_chunks``, unrolled), so
every value it computes on stays register-sized. Edges pad to a multiple of 8 with
zero-weight self-loops at vertex 0, which contribute
``0 * (x_0 - x_0) = 0`` exactly — this is what lets callers pad
per-round edge arrays to a static E_max inside ``lax.scan``. The edge
tables are whole-array SMEM operands: the walk reads one scalar per
edge at a dynamic index, which TPU scalar memory serves and a VMEM lane
load cannot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gossip_mix import lane_chunks, row_tile_cols

_EDGE_PAD = 8


def _edges_kernel(src_ref, dst_ref, w_ref, x_ref, o_ref, *, num_edges: int):
    rows, width = x_ref.shape
    size = x_ref.dtype.itemsize

    def copy(cols):
        o_ref[:, cols] = x_ref[:, cols]

    lane_chunks(width, rows, size, copy)

    def body(e, carry):
        s = src_ref[e]
        d = dst_ref[e]
        we = w_ref[e]

        def mix(cols):
            xs = x_ref[pl.ds(s, 1), cols].astype(jnp.float32)
            xd = x_ref[pl.ds(d, 1), cols].astype(jnp.float32)
            cur = o_ref[pl.ds(d, 1), cols].astype(jnp.float32)
            o_ref[pl.ds(d, 1), cols] = (cur + we * (xs - xd)).astype(
                o_ref.dtype)

        # unrolled: the chunks of one edge then pipeline their loads
        # (18.6 -> 11.1 ms a call at W=12, P = 51,126,720 on a v5e)
        lane_chunks(width, 1, size, mix, unroll=True)
        return carry

    jax.lax.fori_loop(0, num_edges, body, 0)


def pad_edges(src, dst, w, e_max: int | None = None):
    """Pad directed edge arrays to ``e_max`` (>= len, rounded up to a
    multiple of 8, min 8) with zero-weight self-loops at vertex 0 —
    exact no-ops under the kernel, so padded and unpadded calls agree
    bit-for-bit. Returns (src, dst, w) int32/int32/f32."""
    src = jnp.asarray(src, jnp.int32).reshape(-1)
    dst = jnp.asarray(dst, jnp.int32).reshape(-1)
    w = jnp.asarray(w, jnp.float32).reshape(-1)
    e = src.shape[0]
    target = e if e_max is None else max(e_max, e)
    ep = max(_EDGE_PAD, -(-target // _EDGE_PAD) * _EDGE_PAD)
    if ep != e:
        src = jnp.pad(src, (0, ep - e))
        dst = jnp.pad(dst, (0, ep - e))
        w = jnp.pad(w, (0, ep - e))
    return src, dst, w


def gossip_edges(x, src, dst, w, *, interpret: bool = False):
    """x: [W, C]; src, dst: [E] int32 directed edges; w: [E] f32.

    Returns ``y`` with ``y[i] = x[i] + Σ_{e: dst_e=i} w_e (x[src_e] - x[i])``,
    the edges applied in table order. W and C need not be tile
    multiples: the row block is all W rows, and Pallas masks the ragged
    last column tile."""
    r, c = x.shape
    src, dst, w = pad_edges(src, dst, w)
    bc = row_tile_cols(r, c, x.dtype.itemsize)
    kernel = functools.partial(_edges_kernel, num_edges=src.shape[0])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(c, bc),),
        in_specs=[smem, smem, smem,
                  pl.BlockSpec((r, bc), lambda j: (0, j))],
        out_specs=pl.BlockSpec((r, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        interpret=interpret,
    )(src, dst, w, x)

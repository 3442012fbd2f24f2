"""Byzantine-robust gossip kernel: gather-sort-trim on [W, C].

For each worker i the kernel robust-averages the closed neighborhood
``{x_i} ∪ {t_j : j ∈ N(i)}`` coordinate-wise — own honest row plus the
TRANSMITTED neighbor rows — replacing the weighted Eq. 5 mix when
``cfg.robust`` is ``trimmed:<b>`` or ``median``. The neighborhood
arrives as a host-built max-degree padded index table (``nbr [W, D]``,
``deg [W]``), which makes the whole sort/trim window shape-static and
therefore scannable inside the fused round loop.

Grid: one program per column tile (the ``gossip_edges`` layout — all
padded W rows of the tile stay resident). Each program walks the
workers with a ``fori_loop``; per worker it gathers the own row plus
D transmitted rows via dynamic row slices (``pl.ds``) into a window of
``D + 1`` separate ``[1, BC]`` rows (D is static, so the gather is
unrolled and no window buffer is updated in place), masks padding slots
(index >= deg) to +inf, sorts the window with an odd-even transposition
network (D + 1 static passes of elementwise min/max compare-exchanges
— no data-dependent control flow, so it lowers the same everywhere),
and reduces the sorted window:

- ``trimmed``: average of positions ``[b_i, cnt - b_i)`` where
  ``cnt = deg + 1`` and ``b_i`` is the per-worker clamped trim count;
- ``median``: mean of the two middle order statistics.

The neighbour and degree tables are whole-array SMEM operands: the walk
reads them one scalar at a time at dynamic indices.

Workers with no neighbors (including padded rows) keep their row
exactly, so row padding is a no-op like the zero-weight padding edges
of ``gossip_edges``. Oracle: ``ref.robust_gossip_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_COLS = 256        # all W rows stay resident per program: keep tiles lean


def _sort_rows(rows):
    """Odd-even transposition sort of a list of [1, BC] rows (ascending,
    one independent network per column): ``len(rows)`` static passes of
    elementwise compare-exchanges — +inf padding rows sink to the end."""
    rows = list(rows)
    n = len(rows)
    for p in range(n):
        for r in range(p % 2, n - 1, 2):
            lo, hi = rows[r], rows[r + 1]
            rows[r], rows[r + 1] = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
    return rows


def _robust_kernel(nbr_ref, deg_ref, x_ref, t_ref, o_ref, *,
                   num_workers: int, d_pad: int, b: float, mode: str):
    """Per-column-tile program: gather-sort-trim every worker's window."""
    inf = jnp.float32(jnp.inf)

    def worker(i, carry):
        d = deg_ref[i]
        own = x_ref[pl.ds(i, 1), :].astype(jnp.float32)          # [1, bc]
        win = [own]
        for k in range(d_pad):
            j = nbr_ref[i * d_pad + k]
            row = t_ref[pl.ds(j, 1), :].astype(jnp.float32)
            win.append(jnp.where(k < d, row, inf))
        win = _sort_rows(win)
        cnt = d + 1
        if mode == "trimmed":
            if b < 1.0:
                bi = jnp.floor(b * cnt.astype(jnp.float32)).astype(jnp.int32)
            else:
                bi = jnp.int32(int(b))
            bi = jnp.minimum(bi, (cnt - 1) // 2)
            y = jnp.zeros_like(own)
            for pos, row in enumerate(win):
                inside = (pos >= bi) & (pos < cnt - bi)
                y = y + jnp.where(inside & jnp.isfinite(row), row, 0.0)
            y = y / (cnt - 2 * bi)
        else:                                                    # median
            lo = (cnt - 1) // 2
            hi = cnt // 2
            vlo = vhi = win[0]
            for pos, row in enumerate(win[1:], 1):
                vlo = jnp.where(pos == lo, row, vlo)
                vhi = jnp.where(pos == hi, row, vhi)
            y = 0.5 * (vlo + vhi)
        y = jnp.where(d > 0, y, own)
        o_ref[pl.ds(i, 1), :] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, num_workers, worker, 0)


def robust_gossip(x, t, nbr, deg, *, b: float, mode: str,
                  interpret: bool = False):
    """x, t: [W, C]; nbr: [W, D] int32 padded neighbor table; deg: [W].

    Returns the robust-aggregated [W, C] matrix (f32): per worker the
    ``mode`` statistic ("trimmed" with trim knob ``b``, or "median") of
    its own row in ``x`` plus the transmitted rows ``t[nbr[i, :deg[i]]]``.
    W and C need not be tile multiples — rows pad to a multiple of 8
    with degree-0 (keep-own-row) workers, columns to the tile size."""
    r, c = x.shape
    d_pad = max(nbr.shape[1], 1)
    rp = -(-r // 8) * 8
    bc = min(BLOCK_COLS, c)
    cp = -(-c // bc) * bc
    x = x.astype(jnp.float32)
    t = t.astype(jnp.float32)
    nbr = jnp.asarray(nbr, jnp.int32).reshape(r, -1)
    deg = jnp.asarray(deg, jnp.int32)
    if (rp, cp) != (r, c):
        x = jnp.pad(x, ((0, rp - r), (0, cp - c)))
        t = jnp.pad(t, ((0, rp - r), (0, cp - c)))
    if rp != r:
        nbr = jnp.pad(nbr, ((0, rp - r), (0, 0)))
        deg = jnp.pad(deg, (0, rp - r))
    kernel = functools.partial(_robust_kernel, num_workers=rp,
                               d_pad=d_pad, b=b, mode=mode)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(cp // bc,),
        in_specs=[smem, smem,
                  pl.BlockSpec((rp, bc), lambda j: (0, j)),
                  pl.BlockSpec((rp, bc), lambda j: (0, j))],
        out_specs=pl.BlockSpec((rp, bc), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rp, cp), jnp.float32),
        interpret=interpret,
    )(nbr.reshape(rp * d_pad), deg, x, t)
    if (rp, cp) != (r, c):
        out = out[:r, :c]
    return out

"""Pallas kernel validation (interpret=True on CPU) against ref.py oracles:
fixed-shape allclose + hypothesis sweeps over shapes/dtypes (deliverable c).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
# hypothesis is optional (dev dependency): the guard skips only the
# property tests when it is absent, plain tests still run
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.consensus_dist import consensus_dist_2d
from repro.kernels.gossip_mix import (ROW_TILE_VMEM, gossip_mix_2d,
                                      gossip_mix_rows, row_tile_cols)
from repro.kernels.quantize_block import (BLOCK_COLS, BLOCK_ROWS,
                                          dequantize_block_2d,
                                          quantize_block_2d)

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_attention_vs_ref(dtype, causal, window):
    b, hq, hkv, s, hd = 2, 4, 2, 256, 64
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, hq, s, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (b, hkv, s, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (b, hkv, s, hd), jnp.float32).astype(dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=128, block_k=128, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=8, deadline=None)
@given(
    s_blocks=st.integers(1, 3),
    hq_groups=st.sampled_from([(2, 1), (4, 2), (8, 2)]),
    hd=st.sampled_from([64, 128]),
    causal=st.booleans(),
)
def test_flash_attention_hypothesis(s_blocks, hq_groups, hd, causal):
    hq, hkv = hq_groups
    s = 128 * s_blocks
    q = jax.random.normal(KEY, (1, hq, s, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, hkv, s, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, hkv, s, hd))
    out = flash_attention_fwd(q, k, v, causal=causal, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_ops_wrapper_padding():
    """ops.flash_attention pads ragged seq lens and matches the model-layout
    reference used by the transformer stack."""
    from repro.models import layers as L
    b, s, hq, hkv, hd = 1, 100, 4, 2, 64       # s=100: needs padding
    q = jax.random.normal(KEY, (b, s, hq, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (b, s, hkv, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, hkv, hd))
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    mask = L.gqa_scores_mask(s, s, causal=True, window=0)
    want = L.gqa_attention_ref(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# gossip mix
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 6), rows=st.integers(1, 3),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_gossip_mix_hypothesis(k, rows, dtype):
    r, c = 8 * rows, 1024
    x = jax.random.normal(KEY, (r, c), jnp.float32).astype(dtype)
    u = jax.random.normal(jax.random.fold_in(KEY, k), (k, r, c),
                          jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(KEY, 9), (k,),
                           minval=0.0, maxval=1.0 / (k + 1))
    out = gossip_mix_2d(x, u, w, interpret=True)
    want = ref.gossip_mix_ref(x, u, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_gossip_mix_flat_wrapper():
    n = 5000                                  # ragged -> padding path
    x = jax.random.normal(KEY, (n,))
    u = jax.random.normal(jax.random.fold_in(KEY, 1), (3, n))
    w = jnp.array([0.2, 0.3, 0.1])
    out = ops.gossip_mix(x, u, w, interpret=True)
    want = x + jnp.tensordot(w, u - x[None], axes=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_gossip_mix_preserves_average():
    """Doubly-stochastic mixing preserves the network average (the DFL
    invariant behind Eq. 5)."""
    n = ops.TILE
    x0 = jax.random.normal(KEY, (n,))
    x1 = jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    w = jnp.array([0.5])
    y0 = ops.gossip_mix(x0, x1[None], w, interpret=True)
    y1 = ops.gossip_mix(x1, x0[None], w, interpret=True)
    np.testing.assert_allclose(np.asarray(y0 + y1), np.asarray(x0 + x1),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# consensus distance
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 5), rows=st.integers(1, 3))
def test_consensus_dist_hypothesis(k, rows):
    r, c = 8 * rows, 1024
    x = jax.random.normal(KEY, (r, c))
    u = jax.random.normal(jax.random.fold_in(KEY, k + 7), (k, r, c))
    out = consensus_dist_2d(x, u, interpret=True)
    want = ref.consensus_dist_ref(x, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4)


def test_consensus_dist_flat_matches_norm():
    n = 3000
    x = jax.random.normal(KEY, (n,))
    u = jax.random.normal(jax.random.fold_in(KEY, 5), (2, n))
    out = ops.consensus_dist(x, u, interpret=True)
    want = jnp.linalg.norm(u - x[None], axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4)


# ---------------------------------------------------------------------------
# int8 block quantize
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 4), scale=st.floats(1e-3, 1e3))
def test_quantize_roundtrip_hypothesis(rows, scale):
    r, c = BLOCK_ROWS * rows, BLOCK_COLS
    x = jax.random.normal(KEY, (r, c)) * scale
    q, s = quantize_block_2d(x, interpret=True)
    qr, sr = ref.quantize_block_ref(x, BLOCK_ROWS, BLOCK_COLS)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s).ravel(),
                               np.asarray(sr).ravel(), rtol=1e-6)
    # round trip error bounded by scale/2 per element
    y = dequantize_block_2d(q, s, interpret=True)
    err = np.abs(np.asarray(y) - np.asarray(x))
    bound = np.repeat(np.repeat(np.asarray(s), BLOCK_ROWS, 0),
                      BLOCK_COLS, 1) * 0.5 + 1e-6
    assert (err <= bound).all()


def test_quantize_flat_roundtrip():
    n = 10_000
    x = jax.random.normal(KEY, (n,)) * 3.0
    q, s, n_out = ops.quantize(x, interpret=True)
    y = ops.dequantize(q, s, n, interpret=True)
    assert y.shape == x.shape
    # max error = half an int8 step of the per-tile scale
    assert float(jnp.max(jnp.abs(y - x))) <= float(jnp.max(s)) * 0.51


# ---------------------------------------------------------------------------
# padding shim + dense-gossip parity (the fused engine's hot path)
# ---------------------------------------------------------------------------

def _mixing_rows(n_workers: int, k: int, seed: int) -> np.ndarray:
    """Row-stochastic mixing matrix where each worker has ~k neighbors."""
    rng = np.random.default_rng(seed)
    w = np.zeros((n_workers, n_workers))
    for i in range(n_workers):
        nbrs = rng.choice([j for j in range(n_workers) if j != i],
                          size=min(k, n_workers - 1), replace=False)
        w[i, nbrs] = 1.0 / (n_workers + 1)
    np.fill_diagonal(w, 1.0 - w.sum(1))
    return w


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("p", [1024 * 8, 5000, 1000])  # aligned + ragged
def test_gossip_kernel_matches_dense_gossip(dtype, k, p):
    """Per-worker gossip_mix_2d over mixing-matrix rows == the reference
    engine's dense ``_gossip`` (tensordot) on the stacked parameters.
    Non-tile-multiple p exercises the kernel's padding shim."""
    from repro.core.engine import _gossip
    n_workers = 6
    mix = _mixing_rows(n_workers, k, seed=p + k)
    x = jax.random.normal(KEY, (n_workers, p), jnp.float32).astype(dtype)

    want = _gossip({"w": x}, jnp.asarray(mix, jnp.float32))["w"]

    cols = min(1024, p)
    rows = -(-p // cols)
    x2 = jnp.pad(x, ((0, 0), (0, rows * cols - p))).reshape(
        n_workers, rows, cols)
    y2 = jax.vmap(lambda xi, wi: gossip_mix_2d(
        xi, x2, wi, interpret=True))(x2, jnp.asarray(mix, jnp.float32))
    got = y2.reshape(n_workers, -1)[:, :p]

    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_workers,p", [(6, 1024 * 8), (6, 5000),
                                         (30, 1000), (4, 300), (9, 1537),
                                         (12, 70_001)])  # 3 tiles, ragged
def test_gossip_mix_rows_matches_vmapped_mix_2d(dtype, n_workers, p):
    """The fused engine's dense mix on the [W, P] rows is, element for
    element, the same arithmetic as ``gossip_mix_2d`` vmapped over the
    mixing-matrix rows on the [W, rows, 1024] layout: bit-identical,
    ragged last column tile included, and within tolerance of the
    reference engine's dense ``_gossip`` (tensordot)."""
    from repro.core.engine import _gossip
    mix = jnp.asarray(_mixing_rows(n_workers, 3, seed=p), jnp.float32)
    x = jax.random.normal(KEY, (n_workers, p), jnp.float32).astype(dtype)

    got = gossip_mix_rows(x, mix, interpret=True)

    cols = min(1024, p)
    rows = -(-p // cols)
    x2 = jnp.pad(x, ((0, 0), (0, rows * cols - p))).reshape(
        n_workers, rows, cols)
    y2 = jax.vmap(lambda xi, wi: gossip_mix_2d(
        xi, x2, wi, interpret=True))(x2, mix)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(y2.reshape(n_workers, -1)[:, :p],
                                             np.float32))

    want = _gossip({"w": x}, mix)["w"]
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("n_workers,want", [(4, 65_536), (12, 32_768),
                                            (30, 16_384), (2048, 256)])
def test_row_tile_cols_fills_the_vmem_budget(n_workers, want):
    """The column tile of the row-layout kernels: whole 128-lane tiles
    whose double-buffered input and output blocks, rows padded to 8
    sublanes, fit the budget, as wide as that allows and never wider
    than the array."""
    p = 51_126_720
    bc = row_tile_cols(n_workers, p, 4)
    assert bc == want and bc % 128 == 0
    rows = -(-n_workers // 8) * 8
    assert 2 * 2 * rows * bc * 4 <= ROW_TILE_VMEM
    assert 2 * 2 * rows * (bc + 128) * 4 > ROW_TILE_VMEM
    assert row_tile_cols(n_workers, 100, 4) == 100
    assert row_tile_cols(n_workers, bc, 4) == bc


@pytest.mark.parametrize("shape", [(12, 1000), (7, 1024), (5, 100),
                                   (20, 2100)])
def test_gossip_mix_2d_padding_shim(shape):
    """Shapes off the (8, 1024) tile grid — the case the old
    ``assert r % br == 0`` rejected — still match the jnp oracle."""
    r, c = shape
    k = 3
    x = jax.random.normal(KEY, (r, c))
    u = jax.random.normal(jax.random.fold_in(KEY, r), (k, r, c))
    w = jnp.array([0.25, 0.1, 0.3])
    out = gossip_mix_2d(x, u, w, interpret=True)
    assert out.shape == (r, c)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.gossip_mix_ref(x, u, w)),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(r=st.integers(1, 30), c=st.sampled_from([100, 1000, 1024, 1100]),
       scale=st.floats(1e-2, 1e2))
def test_quantize_padding_shim_roundtrip(r, c, scale):
    """quantize/dequantize round trip with the padding shim: arbitrary
    [R, C] stays within half an int8 step of each tile's scale, and the
    scale grid covers ceil-divided tiles."""
    x = jax.random.normal(KEY, (r, c)) * scale
    q, s = quantize_block_2d(x, interpret=True)
    br, bc = min(BLOCK_ROWS, r), min(BLOCK_COLS, c)
    assert q.shape == (r, c)
    assert s.shape == (-(-r // br), -(-c // bc))
    y = dequantize_block_2d(q, s, interpret=True)
    assert y.shape == (r, c)
    err = np.abs(np.asarray(y) - np.asarray(x))
    # per-tile bound: expand each tile's scale back over its elements
    s_np = np.asarray(s)
    bound = np.repeat(np.repeat(s_np, br, 0), bc, 1)[:r, :c] * 0.5 + 1e-6
    assert (err <= bound).all()

"""Blocked online-softmax attention (flash attention) for TPU.

Forward kernel, GQA-aware, causal and sliding-window masking. Grid is
(batch, q_heads, q_blocks, kv_blocks) with the kv dimension marked
"arbitrary" (sequential-minor on TPU), so the running-max / denominator /
accumulator live in VMEM scratch carried across kv iterations — the
canonical TPU flash pattern. Block shapes are MXU-aligned (multiples of
128 on the sequence dims; head_dim is the lane dim).

HBM->VMEM traffic per q block: q once, k/v streamed once — O(S·hd) per
head instead of the O(S^2) score materialization of naive attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: int, block_q: int,
                 block_k: int, kv_blocks: int):
    qi = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # [BQ, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [BK, hd]
    v = v_ref[0, 0].astype(jnp.float32)                  # [BK, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # [BQ, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # [BQ, BK]
    alpha = jnp.exp(m_prev - m_new)                      # [BQ, 1]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """q: [B, Hq, S, hd]; k, v: [B, Hkv, Sk, hd]. Returns [B, Hq, S, hd].

    Hq must be a multiple of Hkv (GQA); S, Sk multiples of the block sizes
    (ops.py pads). Mask conventions match ``layers.gqa_scores_mask``.
    """
    b, hq, s, hd = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    assert s % block_q == 0 and sk % block_k == 0, (s, sk, block_q, block_k)
    q_blocks, kv_blocks = s // block_q, sk // block_k
    scale = hd ** -0.5

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_blocks=kv_blocks)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, h, qi, kb: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, qi, kb, g=g: (bi, h // g, kb, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, qi, kb, g=g: (bi, h // g, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, h, qi, kb: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)

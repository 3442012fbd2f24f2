"""Plain reference of the dense decoder worker (Llama-style GQA block).

Straight ``jax.numpy``: no kernels, no scans over layers, no remat, one
chunk of logits. It follows the block the configuration file describes
(RMSNorm with a ``1 + gamma`` scale, rotary positions on the first and
second halves of each head, grouped-query causal attention, SiLU-gated
MLP, untied output head) and the initialisation the file states, so it
builds its own weights from the seed.

``dtype`` is the precision the reference computes and stores in:
float32 under ``jax.default_matmul_precision("highest")`` is the
reference; bfloat16 is the control of the correctness check.

``forward_flops_per_row`` and ``param_count`` are the block's counts,
which ``bench/counts.py`` reads for every configuration of this kind;
``tiny`` is its cut to CPU size, which ``bench/checks/tiny.py`` reads.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def forward_flops_per_row(model: dict, seq: int) -> float:
    """Forward FLOPs for one row of ``seq`` tokens (``seq - 1`` predicted
    positions). Multiply-adds count two."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    s = seq - 1
    proj = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    # causal attention: each position scores and reads its prefix
    attn = 2 * 2 * h * hd * (s + 1) / 2
    per_pos = (2 * proj + attn) * model["num_hidden_layers"] \
        + 2 * d * model["vocab_size"]
    return per_pos * s


def param_count(model: dict) -> int:
    """P, one worker's parameters: the leaves ``init`` builds."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f + 2 * d
    return (model["num_hidden_layers"] * layer
            + 2 * d * model["vocab_size"] + d)


TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 64}


def tiny(model: dict) -> dict:
    """The configuration cut to a size the CPU runs in seconds, with the
    program's model spec rebuilt to match."""
    cfg = {**model, **TINY}
    cfg["program_spec"] = (
        f"dense:d={cfg['hidden_size']},heads={cfg['num_attention_heads']},"
        f"kv={cfg['num_key_value_heads']},ff={cfg['intermediate_size']},"
        f"layers={cfg['num_hidden_layers']},vocab={cfg['vocab_size']}")
    return cfg


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    """One worker's parameters from ``seed``: normal draws of std
    ``1/sqrt(fan_in)`` for projections, 0.02 for the embedding, zeros for
    the norm scales; keys split as the configuration's ``init`` states."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    v, n_layers = cfg["vocab_size"], cfg["num_hidden_layers"]

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    def block(key):
        k_attn, k_mlp, _, _ = jax.random.split(key, 4)
        ka = jax.random.split(k_attn, 4)
        km = jax.random.split(k_mlp, 3)
        return {
            "attn": {"wq": normal(ka[0], (d, h * hd), 1 / math.sqrt(d)),
                     "wk": normal(ka[1], (d, kv * hd), 1 / math.sqrt(d)),
                     "wv": normal(ka[2], (d, kv * hd), 1 / math.sqrt(d)),
                     "wo": normal(ka[3], (h * hd, d), 1 / math.sqrt(h * hd))},
            "ln1": jnp.zeros((d,), dtype),
            "ln2": jnp.zeros((d,), dtype),
            "mlp": {"w_up": normal(km[0], (d, f), 1 / math.sqrt(d)),
                    "w_down": normal(km[1], (f, d), 1 / math.sqrt(f)),
                    "w_gate": normal(km[2], (d, f), 1 / math.sqrt(d))},
        }

    k_emb, k_blocks, k_head, _, _ = jax.random.split(key, 5)
    blocks = [block(k) for k in jax.random.split(k_blocks, n_layers)]
    return {
        "blocks": jax.tree.map(lambda *ls: jnp.stack(ls), *blocks),
        "embed": normal(k_emb, (v, d), 0.02),
        "lm_head": normal(k_head, (d, v), 1 / math.sqrt(d)),
        "ln_f": jnp.zeros((d,), dtype),
    }


def _rms_norm(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x: [B, S, heads, hd]; rotates (first half, second half) pairs."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _attention(p, x, cfg):
    b, s, _ = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    q = _rope((x @ p["wq"]).reshape(b, s, h, hd), cfg["rope_theta"])
    k = _rope((x @ p["wk"]).reshape(b, s, kv, hd), cfg["rope_theta"])
    v = (x @ p["wv"]).reshape(b, s, kv, hd)
    # query head j reads key/value head j // (h // kv)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, h * hd)
    return o @ p["wo"]


def loss(params, tokens, labels, cfg: dict):
    """Mean next-token cross-entropy of ``tokens`` ([..., S] int): the
    first S-1 positions predict the last S-1. ``labels`` (the document
    class) takes no part in the loss."""
    del labels
    tokens = tokens.reshape((-1, tokens.shape[-1])).astype(jnp.int32)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    eps = cfg["rms_norm_eps"]
    x = params["embed"][inputs]
    for layer in range(cfg["num_hidden_layers"]):
        bp = jax.tree.map(lambda l: l[layer], params["blocks"])
        x = x + _attention(bp["attn"], _rms_norm(x, bp["ln1"], eps), cfg)
        hmid = _rms_norm(x, bp["ln2"], eps)
        m = bp["mlp"]
        x = x + (jax.nn.silu(hmid @ m["w_gate"]) * (hmid @ m["w_up"])) \
            @ m["w_down"]
    logits = (_rms_norm(x, params["ln_f"], eps) @ params["lm_head"]
              ).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


"""Operations and bytes the benchmark's work requires, from shapes alone,
and the table of device peaks they are held against.

Counts are of the work an experiment requires, not of what the program
happens to execute: local SGD at each worker's planned tau (not the
masked steps up to the bucketed scan length), one forward pass per
evaluation, Alg. 1's three gradients per worker, and the mixing.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def forward_flops_per_row(model: dict, seq: int) -> float:
    """Forward FLOPs for one row of the model's batch, a sequence of
    ``seq`` tokens (``seq - 1`` predicted positions). Multiply-adds count
    two."""
    if model["kind"] == "dense":
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
    raise ValueError(f"no FLOP count for model kind {model['kind']!r}")


def param_count(model: dict) -> int:
    """P, the length of one worker's row of the [W, P] matrix."""
    if model["kind"] == "dense":
        d, f = model["hidden_size"], model["intermediate_size"]
        h, kv = model["num_attention_heads"], model["num_key_value_heads"]
        hd = d // h
        layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f + 2 * d
        return (model["num_hidden_layers"] * layer
                + 2 * d * model["vocab_size"] + d)
    raise ValueError(f"no parameter count for model kind {model['kind']!r}")


def round_flops(model: dict, traffic: dict, taus_sum: float,
                links: int, eval_rows: int, measured: bool) -> float:
    """FLOPs one round requires: local SGD (forward + backward, three
    forwards' worth, per step), one evaluation forward per worker on the
    test rows, gossip (two per link end and parameter) and, for an
    adaptive strategy, Alg. 1: two gradients on the whole fleet's
    evaluation stack and one on the probe, per worker."""
    w, seq = traffic["workers"], traffic["seq"]
    fwd = forward_flops_per_row(model, seq)
    p = param_count(model)
    sgd = 3 * fwd * traffic["batch"] * taus_sum
    evaluation = fwd * eval_rows * w
    mixing = 2 * 2 * links * p
    total = sgd + evaluation + mixing
    if measured:
        total += w * 3 * fwd * (2 * 256 * w + 32 * w)
    return total


def mix_rows_bytes(workers: int, p: int) -> float:
    """Least HBM bytes of one dense mix: read [W, P] and the [W, W]
    matrix once, write [W, P] once (float32)."""
    return 4.0 * (2 * workers * p + workers * workers)


def mix_edges_bytes(workers: int, p: int, edges: int) -> float:
    """Least HBM bytes of one edge-list mix: read [W, P] and the three
    edge tables once, write [W, P] once (float32 / int32)."""
    return 4.0 * (2 * workers * p + 3 * edges)

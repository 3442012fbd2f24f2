"""Operations and bytes the benchmark's work requires, from shapes alone,
and the table of device peaks they are held against.

Counts are of the work an experiment requires, not of what the program
happens to execute: local SGD at each worker's planned tau (not the
masked steps up to the bucketed scan length), one forward pass per
evaluation, Alg. 1's three gradients per worker, and the mixing.

What one worker's model costs comes from the configuration's plain
reference, ``reference/<config["reference"]>.py``, which defines
``forward_flops_per_row(model, seq)`` and ``param_count(model)`` beside
its ``init`` and ``loss``: a model of a new kind brings its counts with
its reference, and nothing here changes. The modules are found by
``reference.load``.
"""
from __future__ import annotations

import json
from pathlib import Path

import reference

BENCH = Path(__file__).resolve().parent
PEAKS = BENCH / "peaks.json"
MODEL_COUNTS = ("forward_flops_per_row", "param_count")


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def model_counts(model: dict):
    """The reference module of ``model``, which must define both of
    ``MODEL_COUNTS``."""
    return reference.model_module(
        model, MODEL_COUNTS,
        "forward_flops_per_row(model, seq) (forward FLOPs of one row of seq "
        "tokens) and param_count(model) (P, one worker's parameters)")


def forward_flops_per_row(model: dict, seq: int) -> float:
    """Forward FLOPs for one row of the model's batch, a sequence of
    ``seq`` tokens, by the configuration's reference module."""
    return model_counts(model).forward_flops_per_row(model, seq)


def param_count(model: dict) -> int:
    """P, the length of one worker's row of the [W, P] matrix, by the
    configuration's reference module."""
    return model_counts(model).param_count(model)


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

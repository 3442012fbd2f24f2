"""Self-check of the work counts (``bench/counts.py``), which read one
worker's parameters and FLOPs from the configuration's reference module.

- ``smollm360m-l4v8``: P is 51,126,720, and every cell's FLOPs a round
  equal those of the dense formula as ``counts.py`` held it before the
  counts moved into ``reference/dense.py`` (copied below as the witness),
  over a spread of taus, links and evaluation rows, with and without
  Alg. 1;
- every configuration's P equals the leaf sizes of its reference
  module's own ``init``, under ``jax.eval_shape`` (no weights are made);
- a reference module that defines no counts, or no ``tiny``, raises an
  error naming the module and the functions to add;
- every cell's CPU-size cut (``tiny.py``) comes from its reference
  module's ``tiny`` and agrees with that module's ``init``.

    python3 bench/checks/check_counts.py
"""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]

import counts  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

SMOLLM_P = 51_126_720


def dense_forward_flops_per_row(model: dict, seq: int) -> float:
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    s = seq - 1
    proj = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    attn = 2 * 2 * h * hd * (s + 1) / 2
    per_pos = (2 * proj + attn) * model["num_hidden_layers"] \
        + 2 * d * model["vocab_size"]
    return per_pos * s


def dense_param_count(model: dict) -> int:
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f + 2 * d
    return (model["num_hidden_layers"] * layer
            + 2 * d * model["vocab_size"] + d)


def dense_round_flops(model, traffic, taus_sum, links, eval_rows,
                      measured) -> float:
    w, seq = traffic["workers"], traffic["seq"]
    fwd = dense_forward_flops_per_row(model, seq)
    total = (3 * fwd * traffic["batch"] * taus_sum + fwd * eval_rows * w
             + 2 * 2 * links * dense_param_count(model))
    if measured:
        total += w * 3 * fwd * (2 * 256 * w + 32 * w)
    return total


def cells() -> list:
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    return [run.load_cell(w["name"]) for w in spec["workloads"]]


def check_dense() -> None:
    n = 0
    for cell in cells():
        model, p = cell.config, cell.traffic
        if model["reference"] != "dense":
            continue
        if model["name"] == "smollm360m-l4v8":
            assert counts.param_count(model) == SMOLLM_P, \
                counts.param_count(model)
        for taus_sum in (1.0, 9.5, p["workers"] * p["tau_max"]):
            for links in (0, 3, 2 * p["workers"]):
                for eval_rows in (256, p["eval_rows"]):
                    for measured in (False, True):
                        args = (model, p, taus_sum, links, eval_rows,
                                measured)
                        assert counts.round_flops(*args) == \
                            dense_round_flops(*args), (cell.name, args[2:])
                        n += 1
    print(f"ok: dense P {SMOLLM_P:,}; FLOPs a round as before in {n} cases")


def check_against_init() -> None:
    import jax
    seen = set()
    for cell in cells():
        model = cell.config
        if model["name"] in seen:
            continue
        seen.add(model["name"])
        ref = reference.load(model["reference"])
        shapes = jax.eval_shape(lambda k: ref.init(model, k),
                                jax.random.PRNGKey(0))
        leaves = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
        assert counts.param_count(model) == leaves, \
            (model["name"], counts.param_count(model), leaves)
        print(f"ok: {model['name']}: P {leaves:,} = the leaves of "
              f"reference/{model['reference']}.py's init")


def check_missing() -> None:
    """``reference/clock.py`` is a reference without a model, so it has
    no counts and no CPU-size cut."""
    import tiny
    model = {"name": "no-counts", "reference": "clock"}
    for fn, parts in (
            (lambda: counts.param_count(model),
             ("forward_flops_per_row", "param_count")),
            (lambda: counts.forward_flops_per_row(model, 16),
             ("forward_flops_per_row", "param_count")),
            (lambda: tiny.tiny_model(model), ("tiny(model)",))):
        try:
            fn()
        except ValueError as e:
            msg = str(e)
        else:
            raise AssertionError("a reference without counts was accepted")
        for part in ("bench/reference/clock.py",) + parts:
            assert part in msg, (part, msg)
        print(f"ok: a reference without it is refused: {msg}")


def check_tiny() -> None:
    """Every configuration's CPU-size cut comes from its reference module
    and keeps P consistent with that module's ``init``."""
    import jax
    import tiny
    for cell in cells():
        model = tiny.tiny_model(cell.config)
        ref = reference.load(model["reference"])
        shapes = jax.eval_shape(lambda k: ref.init(model, k),
                                jax.random.PRNGKey(0))
        leaves = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
        assert counts.param_count(model) == leaves < SMOLLM_P, \
            (cell.name, leaves)
        assert cell.config["program_spec"] != model["program_spec"]
        print(f"ok: {cell.name}: CPU-size cut from "
              f"reference/{model['reference']}.py, P {leaves:,}")


def main() -> int:
    check_dense()
    check_against_init()
    check_missing()
    check_tiny()
    return 0


if __name__ == "__main__":
    sys.exit(main())

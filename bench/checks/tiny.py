"""A benchmark cell cut to a size the CPU runs in seconds, for the checks
in this directory: the harness, the reference and the comparison run
unchanged on it; only the sizes shrink. The model's cut comes from its
reference module's ``tiny(model)``, so a model of a new kind brings it
with its reference."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import reference  # noqa: E402
import run  # noqa: E402


def tiny_model(model: dict) -> dict:
    """``model`` cut by its reference module's ``tiny``."""
    mod = reference.model_module(
        model, ("tiny",),
        "tiny(model) (the configuration cut to a size the CPU runs in "
        "seconds, its program_spec included)")
    return mod.tiny(model)


def tiny(name: str, rounds: int = 4) -> "run.Cell":
    cell = copy.deepcopy(run.load_cell(name))
    cell.config = tiny_model(cell.config)
    # about 33 rows a worker, and 400 at least
    w = cell.traffic["workers"]
    cell.traffic.update(num_samples=max(400, 100 * w // 3),
                        rounds=rounds)
    return cell

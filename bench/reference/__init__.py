"""The plain references the correctness check replays, found by name.

A configuration names its worker model's module in ``"reference"``:
``reference/<name>.py``. Besides ``init`` and ``loss`` such a module
defines ``forward_flops_per_row`` and ``param_count`` (read by
``counts.py``) and ``tiny`` (read by ``checks/tiny.py``), so a model of a
new kind comes in as one new file here.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def load(name: str):
    """The plain reference ``reference/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(model: dict, names: tuple[str, ...], what: str):
    """The reference module of ``model``, which must define every one of
    ``names``; ``what`` says what each is, in the error that names the
    file and the functions to add."""
    mod = load(model["reference"])
    missing = [f for f in names if not hasattr(mod, f)]
    if missing:
        raise ValueError(
            f"bench/reference/{model['reference']}.py defines no "
            f"{' or '.join(missing)}: add {what} to it")
    return mod

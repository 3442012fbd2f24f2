"""A benchmark cell cut to a size the CPU runs in seconds, for the checks
in this directory: the harness, the reference and the comparison run
unchanged on it; only the sizes shrink."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import run  # noqa: E402

DENSE = {"hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 64}


def tiny(name: str, rounds: int = 4) -> "run.Cell":
    cell = run.load_cell(name)
    cell = copy.deepcopy(cell)
    cfg = cell.config
    if cfg["kind"] == "dense":
        cfg.update(DENSE)
        cfg["program_spec"] = (
            f"dense:d={cfg['hidden_size']},heads={cfg['num_attention_heads']},"
            f"kv={cfg['num_key_value_heads']},"
            f"ff={cfg['intermediate_size']},"
            f"layers={cfg['num_hidden_layers']},vocab={cfg['vocab_size']}")
        cell.traffic.update(num_samples=400)
    cell.traffic["rounds"] = rounds
    return cell

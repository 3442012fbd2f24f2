"""Faults planted under the timed path must make ``correct`` come out false.

Drives the harness's run (``run.run_cell``) past its look for a chip, on
each cell cut to CPU size (``tiny.py``) and held to the cell's own
limits, once clean and once with each fault a training cell can have
planted in the program:

- ``state_unchanged``: the local SGD step returns its state unchanged;
- ``half_batch``: each SGD step takes the mean loss over the first half
  of its batch only;
- ``loss_altered``: the round program's fleet loss comes out 1 % high;
- ``clock_altered``: the simulated round time the host records comes
  out high by a millionth.

The clean run must read ``correct`` true and every faulty run false.
(Leaving out the exchange between chips is a fault only of a cell on
several chips; no cell here has one.)

    JAX_PLATFORMS=cpu python3 bench/checks/check_faults.py [--workload <cell>]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent), str(HERE)]

import run  # noqa: E402
import tiny  # noqa: E402

SEED = 2**31 + 4242
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def planted(fault: str):
    """Patch the program for ``fault``; returns the undo function."""
    import jax
    import jax.numpy as jnp

    from repro.core import fused
    sgd, seg = fused._sgd_worker, fused._scan_segment
    pre = fused._precompute_segment

    if fault == "state_unchanged":
        fused._sgd_worker = lambda adapter, params, *a, **k: params
    elif fault == "half_batch":
        def half(adapter, params, bx, by, tau, lr, tau_max):
            b = bx.shape[1] // 2
            return sgd(adapter, params, bx[:, :b], by[:, :b], tau, lr,
                       tau_max)
        fused._sgd_worker = half
    elif fault == "loss_altered":
        def altered(*args, **kw):
            carry, outs = seg(*args, **kw)
            return carry, {**outs, "loss": outs["loss"] * jnp.float32(1.01)}
        fused._scan_segment = altered
    elif fault == "clock_altered":
        def late(*args, **kw):
            segment, clock, stop = pre(*args, **kw)
            segment.round_time = [t * (1 + 1e-6) for t in segment.round_time]
            return segment, clock, stop
        fused._precompute_segment = late
    elif fault != "clean":
        raise ValueError(fault)
    jax.clear_caches()

    def undo():
        fused._sgd_worker, fused._scan_segment = sgd, seg
        fused._precompute_segment = pre
        jax.clear_caches()
    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    names = args.workload or [w["name"] for w in json.loads(
        (run.ROOT / "BENCHMARK.json").read_text())["workloads"]]
    bad = []
    for name in names:
        cell = tiny.tiny(name)
        for fault in ("clean", "state_unchanged", "half_batch",
                      "loss_altered", "clock_altered"):
            undo = planted(fault)
            try:
                res = run.run_cell(cell, SEED, 0.0, False, CPU,
                                   t_start=time.perf_counter())
            finally:
                undo()
            want = fault == "clean"
            failing = [n for n, c in res["checks"].items()
                       if not c["value"] <= c["limit"]]
            print(f"{name} {fault}: correct={res['correct']} (want {want}); "
                  f"failing {failing}", flush=True)
            if res["correct"] != want:
                bad.append((name, fault))
    if bad:
        raise AssertionError(f"faults not caught / clean runs refused: {bad}")
    print("ok: every clean run correct, every planted fault caught")
    return 0


if __name__ == "__main__":
    sys.exit(main())

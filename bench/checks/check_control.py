"""Readings that the correctness limits of a cell are set from.

For each seed, in one process, at the cell's own sizes:

- ``program``: one experiment through the fused engine (the first seed's
  after a warm-up) against the plain reference: the sound runs'
  readings (lower end);
- ``control`` (first ``--control-seeds`` seeds): the reference computed in
  bfloat16 put in the program's place, against the float32 reference:
  the control's readings (upper end);
- ``half_batch`` (same seeds): the reference with each SGD step taking
  the first half of its batch, against the clean reference: that
  fault's readings. A state left unchanged reads 1 on ``change`` and
  needs no run; a loss altered where it is produced reads its alteration;
- ``user_path`` (first seed): the experiment's records against a direct
  ``run_algorithm(..., fused=True)`` call, field by field (both runs on
  the same device, so every field must be identical).

    python3 bench/checks/check_control.py --workload <cell> --seeds 12
    python3 bench/checks/check_control.py --workload <cell> --tiny

It prints one JSON line per seed and a summary, then holds the readings
to the cell's limits (``limits/<cell>.json``): it exits non-zero unless
every program reading passes, the control and ``half_batch`` each fail
one number on every seed they ran, and the user path is identical.
``--tiny`` runs the cell cut to CPU size (``tiny.py``) on three seeds,
all of them with the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent), str(HERE)]

import numpy as np  # noqa: E402

import compare  # noqa: E402
import run  # noqa: E402

SEED0 = 2**31 + 1009


def as_program(replay) -> dict:
    """A reference replay put in the program's place."""
    return {"clock": [replay.clock],
            "loss": [replay.loss], "consensus": [replay.consensus],
            "change": replay.change,
            "observed": [{"update_norms": m["update"], "loss": m["loss"],
                          "smooth_l": m["smooth_l"], "sigma": m["sigma"],
                          "edge_dist": m["edge"]} for m in replay.measured]}


def user_path_equal(cell, inp, hist) -> dict:
    from repro.core.experiment import run_algorithm
    p = inp.params
    direct = run_algorithm(p["strategy"], inp.cfg, non_iid_p=p["non_iid_p"],
                           rounds=p["rounds"], spread=p["spread"],
                           fused=True, num_samples=p["num_samples"],
                           mesh=inp.mesh)
    direct.final_params = None
    a, b = hist.as_arrays(), direct.as_arrays()
    return {k: bool(np.array_equal(a[k], b[k])) for k in a}


def readings_for_seed(cell, seed: int, control: bool, user_path: bool,
                      warm: bool):
    import jax.numpy as jnp
    inp = run.build_inputs(cell, seed)
    out = {}
    t = time.perf_counter()
    if not warm:
        hist, rec = run.run_experiment(inp)
        hist.final_params = None
    hist, rec = run.run_experiment(inp)
    t_prog = time.perf_counter() - t
    records = [[r.__dict__.copy() for r in hist.records]]
    prog = {"clock": [compare.clock_fields(e) for e in records],
            "loss": [[r["loss"] for r in e] for e in records],
            "consensus": [[r["consensus"] for r in e] for e in records],
            "change": compare.program_change(cell, inp, hist.final_params),
            "observed": rec.observed}
    hist.final_params = None
    if user_path:
        out["user_path"] = user_path_equal(cell, inp, hist)
    t = time.perf_counter()
    ref = compare.replay_for(cell, inp, rec.plans)
    t_ref = time.perf_counter() - t
    adaptive = inp.strategy.adaptive
    out["program"] = compare.readings(prog, ref, adaptive)
    out["seconds"] = {"experiments": t_prog, "reference": t_ref}
    if control:
        low = compare.replay_for(cell, inp, rec.plans, dtype=jnp.bfloat16)
        out["control"] = compare.readings(as_program(low), ref, adaptive)
        half = compare.replay_for(cell, inp, rec.plans, fault="half_batch")
        out["half_batch"] = compare.readings(as_program(half), ref, adaptive)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=SEED0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.tiny:
        import tiny
        cell = tiny.tiny(args.workload)
        seeds = [args.first_seed + 7 * i for i in range(3)]
    else:
        cell = run.load_cell(args.workload)
        run.require_chips(cell.chips)
        seeds = [args.first_seed + 7 * i for i in range(args.seeds)]
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for i, seed in enumerate(seeds):
        r = readings_for_seed(cell, seed, i < args.control_seeds or args.tiny,
                              i == 0, i > 0)
        r["seed"] = seed
        rows.append(r)
        print(json.dumps(r), flush=True)
    names = sorted(rows[0]["program"])
    for kind in ("program", "control", "half_batch"):
        have = [r[kind] for r in rows if kind in r]
        print(f"{kind}: " + ", ".join(
            f"{n} max {max(h[n] for h in have):.3g} min "
            f"{min(h[n] for h in have):.3g}" for n in names), flush=True)
    limits = {n: v["limit"] for n, v in cell.limits.items()}
    faults = []
    for r in rows:
        bad = {n: r["program"][n] for n in limits
               if not r["program"][n] <= limits[n]}
        if bad:
            faults.append(f"seed {r['seed']}: the program fails {bad}")
        for kind in ("control", "half_batch"):
            if kind in r and all(r[kind][n] <= limits[n] for n in limits):
                faults.append(f"seed {r['seed']}: {kind} passes every "
                              f"limit: {r[kind]}")
        if "user_path" in r and not all(r["user_path"].values()):
            faults.append(f"seed {r['seed']}: the user path differs: "
                          f"{r['user_path']}")
    if faults:
        print("FAILED against the limits " + json.dumps(limits) + ":\n"
              + "\n".join(faults), flush=True)
        return 1
    print("ok: program within the limits, control and half_batch fail one, "
          "user path identical", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

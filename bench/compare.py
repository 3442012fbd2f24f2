"""The comparison that decides ``correct``.

What the timed experiments produced (every window experiment's per-round
records, the last experiment's final ``[W, P]`` rows and the Alg. 1
measurements its planner received) is held against the plain reference
replaying that experiment (``reference/dfl.py``). Each number is a gap
between the program's reading and the reference's, and is compared with
the cell's limit in ``limits/<cell>.json``; a number without a limit
there is printed but decides nothing.

- ``clock``: the simulated clock of every round of every window
  experiment (``round_time``, ``waiting_time``, ``mean_tau``,
  ``num_links``, ``cumulative_time``), the largest relative gap to the
  plain clock (``reference/clock.py``) under the same plans: host
  float64, so it must be 0;
- ``loss``: per-round fleet test loss, the largest relative gap over
  every round of every window experiment;
- ``consensus``: per-round consensus distance, the largest gap over the
  rounds, relative to the larger of the reference's distance and its
  mean update norm in that round (the complete topology mixes the
  fleet to one point, where a relative gap has no scale);
- ``change``: by the worst (worker, leaf) pair, the gap between the norm
  of the program's change of that leaf over the experiment and the
  reference's, relative to the larger of the reference's norm and the
  median pair's. Pairs whose first gradient in the reference is under a
  thousandth of the median pair's are left out (they move by round-off);
- for an adaptive strategy, over its first measured rounds: ``update``
  (each worker's update norm), ``meas_loss`` (the mean evaluation-stack
  loss), ``smooth_l`` and ``sigma`` (the medians the planner receives),
  and ``edge`` (pairwise distances, relative to the larger of the
  reference's distance, the round's median one and its mean update
  norm).
"""
from __future__ import annotations

import numpy as np

import counts
import reference

MEASURE_ROUNDS = 3
EXCLUDE_BELOW = 1e-3
# what the reference models, by traffic key; any other value is refused
MODELLED = {"strategy": ("fedhp", "dpsgd"), "compress": ("none",),
            "robust": ("none",), "replan_every": (1,)}


def refuse_unmodelled(traffic: dict) -> None:
    """Raise for a traffic mix the reference does not model, so that a
    run never compares the program with a reference of other work."""
    bad = {k: traffic.get(k) for k, ok in MODELLED.items()
           if traffic.get(k) not in ok}
    if bad:
        raise ValueError(f"the reference does not model {bad}; it models "
                         f"{MODELLED}")


def _rel(a, b, scale=None):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    s = np.abs(b) if scale is None else np.maximum(np.abs(b), scale)
    return float(np.max(np.abs(a - b) / np.maximum(s, 1e-30)))


def readings(prog: dict, ref, measured: bool) -> dict:
    """Gaps between ``prog`` (records of each experiment, change norms,
    observations) and the reference ``Replay``."""
    out = {"clock": max(_rel(e[k], ref.clock[k]) for e in prog["clock"]
                        for k in ref.clock)}
    ref_loss = np.array(ref.loss)
    ref_cons = np.array(ref.consensus)
    scale = np.maximum(ref_cons, np.array(ref.update))
    out["loss"] = max(_rel(l, ref_loss) for l in prog["loss"])
    out["consensus"] = max(_rel(c, ref_cons, scale)
                           for c in prog["consensus"])
    grads = np.array(list(ref.first_grad.values()))
    floor = EXCLUDE_BELOW * np.median(grads)
    keys = [k for k, g in ref.first_grad.items() if g >= floor]
    ref_ch = np.array([ref.change[k] for k in keys])
    prog_ch = np.array([prog["change"][k] for k in keys])
    out["change"] = _rel(prog_ch, ref_ch, np.median(ref_ch))
    if measured:
        obs, meas = prog["observed"], ref.measured
        n = len(meas)
        out["update"] = max(_rel(obs[h]["update_norms"], meas[h]["update"])
                            for h in range(n))
        out["meas_loss"] = max(_rel(obs[h]["loss"], meas[h]["loss"])
                               for h in range(n))
        out["smooth_l"] = max(_rel(obs[h]["smooth_l"], meas[h]["smooth_l"])
                              for h in range(n))
        out["sigma"] = max(_rel(obs[h]["sigma"], meas[h]["sigma"])
                           for h in range(n))
        edge = []
        for h in range(n):
            e_ref = meas[h]["edge"]
            off = ~np.eye(len(e_ref), dtype=bool)
            edge.append(_rel(obs[h]["edge_dist"][off], e_ref[off],
                             max(np.median(e_ref[off]), ref.update[h])))
        out["edge"] = max(edge)
    return out


def replay_for(cell, inp, plans, dtype=None, fault: str = ""):
    """The reference's replay of the experiment ``inp`` describes."""
    import jax
    import jax.numpy as jnp

    from reference import dfl
    p = inp.params
    refuse_unmodelled(p)
    with jax.default_matmul_precision("highest"):
        ref = dfl.replay(
            reference.load(cell.config["reference"]), cell.config,
            (inp.train.x, inp.train.y), inp.shards, inp.test_x, inp.test_y,
            seed=inp.cfg.seed, plans=plans, batch=p["batch"], lr=p["lr"],
            lr_decay=p["lr_decay"], tau_max=p["tau_max"],
            eval_subset=p["eval_rows"],
            measure_rounds=MEASURE_ROUNDS if inp.strategy.adaptive else 0,
            dtype=jnp.float32 if dtype is None else dtype, fault=fault,
            chips=cell.chips)
    ref.clock = reference.load("clock").replay(
        plans, seed=inp.cfg.seed, workers=inp.cfg.num_workers,
        params=counts.param_count(cell.config), tau_max=p["tau_max"])
    return ref


def program_change(cell, inp, final_params) -> dict:
    """Norm of each (worker, leaf) change of the program's final rows from
    the initial parameters, which the reference builds from the seed. A
    fleet over several chips is reduced where its rows lie: only the
    [W] norms of each leaf reach the host."""
    import json

    import jax

    from reference import dfl
    f = dfl.programs(reference.load(cell.config["reference"]),
                     json.dumps(cell.config, sort_keys=True),
                     inp.cfg.num_workers, "float32", "", cell.chips)
    with jax.default_matmul_precision("highest"):
        init = f.init(jax.random.PRNGKey(inp.cfg.seed))
        norms = f.rows_norms(final_params, init)
    names = dfl.leaf_names(init)
    return {(wi, names[i]): float(v) for i, n in enumerate(norms)
            for wi, v in enumerate(np.asarray(n))}


def clock_fields(records) -> dict:
    """The simulated clock's fields of one experiment's records."""
    from reference.clock import FIELDS
    return {k: [r[k] for r in records] for k in FIELDS}


def check(cell, inp, hist, rec, records) -> tuple[list[dict], dict]:
    """Reduce the program's output, free its state, run the reference and
    return every reading with its limit (None: printed, not compared),
    and the reference's seconds by phase."""
    prog = {"clock": [clock_fields(e) for e in records],
            "loss": [[r["loss"] for r in e] for e in records],
            "consensus": [[r["consensus"] for r in e] for e in records],
            "change": program_change(cell, inp, hist.final_params),
            "observed": rec.observed}
    hist.final_params = None
    ref = replay_for(cell, inp, rec.plans)
    values = readings(prog, ref, inp.strategy.adaptive)
    return [{"name": name, "value": value,
             "limit": cell.limits.get(name, {}).get("limit")}
            for name, value in values.items()], ref.seconds

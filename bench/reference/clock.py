"""Plain reference of the simulated clock (paper Eq. 10-11).

The fleet is the paper's heterogeneous testbed: each worker runs on one
of five device profiles, picked uniformly from the run's seed. One numpy
generator, seeded with the run's seed, first picks the profiles and then,
round by round, draws every worker's seconds per local step
``mu_i ~ N(mean_i, std_i)`` (floored at 1 ms) and then its uplink
bandwidth ``b_i ~ U(1, 10)`` Mb/s. A link moves the whole model
(32 bits a parameter) at the slower end's bandwidth.

Worker ``i`` needs ``tau_i * mu_i`` plus the time of its slowest link
(nothing without links); the round lasts as long as the slowest worker,
the waiting time is the workers' mean wait for it, and the clock adds
the rounds up. ``mean_tau`` and ``num_links`` are the plan's mean local
steps and undirected links. Host float64 throughout, so the program's
record must agree exactly.
"""
from __future__ import annotations

import numpy as np

# (mean, std) seconds of one local step: workstation, laptop, Xavier NX,
# Jetson TX2, Raspberry Pi 4
PROFILES = ((0.05, 0.005), (0.10, 0.01), (0.20, 0.03), (0.35, 0.05),
            (0.55, 0.10))
BANDWIDTH_MBPS = (1.0, 10.0)
FIELDS = ("round_time", "waiting_time", "mean_tau", "num_links",
          "cumulative_time")


def replay(plans, *, seed: int, workers: int, params: int,
           tau_max: int) -> dict[str, list[float]]:
    """The record's clock fields of each round under ``plans``
    ([(taus, adj)] per round) for a fleet of ``workers`` models of
    ``params`` parameters."""
    rng = np.random.default_rng(seed)
    pick = rng.integers(0, len(PROFILES), workers)
    mean = np.array([PROFILES[i][0] for i in pick])
    std = np.array([PROFILES[i][1] for i in pick])
    bits = 32.0 * params
    out = {k: [] for k in FIELDS}
    total = 0.0
    for taus, adj in plans:
        mu = np.maximum(rng.normal(mean, std), 1e-3)
        bw = rng.uniform(*BANDWIDTH_MBPS, workers) * 1e6
        link = bits / np.minimum(bw[:, None], bw[None, :])
        np.fill_diagonal(link, 0.0)
        taus = np.clip(np.asarray(taus), 1, tau_max)
        adj = np.asarray(adj)
        comm = np.where(adj.sum(1) > 0,
                        np.where(adj > 0, link, 0.0).max(1), 0.0)
        t = taus * mu + comm
        slowest = float(t.max())
        total += slowest
        out["round_time"].append(slowest)
        out["waiting_time"].append(float((slowest - t).mean()))
        out["mean_tau"].append(float(taus.mean()))
        out["num_links"].append(int(adj.sum() // 2))
        out["cumulative_time"].append(total)
    return out

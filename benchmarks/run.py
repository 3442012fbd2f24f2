"""Benchmark harness — one benchmark per paper table/figure (deliverable d).

  fig1   tau sweep: accuracy + completion time vs local updating frequency
  fig2_3 IID convergence + completion time to target accuracy, 5 algorithms
  fig4_5 non-IID (p=0.6 / 0.8) accuracy, 5 algorithms
  fig6   accuracy vs non-IID level
  fig7   average waiting time, 5 algorithms
  kernels  Pallas kernel micro-benches (interpret mode) vs jnp references
  collective  gossip-vs-allreduce wire bytes for the adapted topology
  fused    scan-based engine vs reference engine rounds/sec (D-PSGD shape)
  compressed  int8+error-feedback gossip vs uncompressed: wire bytes,
           accuracy parity, simulated-clock speedup (CI-gated via --smoke)
  sparse   top-k / rand-k sparsified gossip vs uncompressed: wire bytes,
           accuracy parity (CI-gated via --smoke: top-k >= 4x wire at
           <= 1% accuracy drift)
  sparse_gossip  edge-list gossip (cfg.gossip="sparse") vs the dense
           [W, W] matrix: small-W accuracy parity (<= 0.1%) plus a
           W=2048 ring leg the dense engine cannot reach (CI-gated via
           --smoke: wall-clock + memory budgets)
  sharded  sharded [W, P] execution (mesh=...) vs the single-device
           oracle: small-W accuracy parity (<= 0.1%) plus a W=4096
           sparse-ring leg with per-shard memory strictly below the
           whole-array footprint (CI-gated via --smoke on a forced
           8-device CPU)
  adpsgd   fused event-driven AD-PSGD vs the reference event loop:
           events/sec + accuracy parity (CI-gated via --smoke: >= 5x)

Run:  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig6]
Output: CSV lines  benchmark,metric,value  + a summary table.
Quick mode (default) shrinks workers/rounds to finish on one CPU core;
--full uses the paper's 30 workers / full rounds; --smoke shrinks the
fused bench further for CI, where a fused-slower-than-reference result
fails the run (exit 1).
"""
from __future__ import annotations

import argparse
import sys
import time
from dataclasses import replace

import numpy as np

from repro.configs.base import FedHPConfig

ALGOS = ("fedhp", "dpsgd", "ldsgd", "pens", "adpsgd")


SPREAD = 3.0          # class overlap: hard enough that accuracy separates


def base_cfg(full: bool) -> FedHPConfig:
    # paper setup: 30 workers; lr decay 0.993 (CIFAR/IMAGE-100 schedule).
    # quick mode shrinks the cluster so the suite finishes on one CPU core
    if full:
        return FedHPConfig(num_workers=30, rounds=300, tau_init=8,
                           tau_max=30, lr=0.15, lr_decay=0.993,
                           batch_size=32, seed=5)
    return FedHPConfig(num_workers=16, rounds=150, tau_init=8, tau_max=30,
                       lr=0.15, lr_decay=0.993, batch_size=32, seed=5)


def time_budget(full: bool) -> float:
    """Equal-simulated-time comparison (the paper's metric is completion
    TIME; rounds are not comparable across algorithms)."""
    return 300.0 if full else 80.0


def emit(rows, bench, metric, value):
    rows.append((bench, metric, value))
    print(f"{bench},{metric},{value}")


# ---------------------------------------------------------------------------

def bench_fig1(rows, full):
    """Pre-test (Fig. 1): model quality/completion time vs fixed tau."""
    from repro.core.experiment import run_algorithm
    cfg = base_cfg(full)
    taus = (2, 8, 16, 32) if not full else (2, 9, 18, 27, 36, 45)
    for tau in taus:
        c = replace(cfg, tau_init=tau, algorithm="dpsgd")
        h = run_algorithm("dpsgd", c, non_iid_p=0.4, rounds=cfg.rounds,
                          spread=SPREAD, time_budget=time_budget(full))
        emit(rows, "fig1", f"acc@tau={tau}", round(h.final_accuracy, 4))
        t90 = h.completion_time(0.80)
        emit(rows, "fig1", f"time_to_80%@tau={tau}",
             round(t90, 1) if t90 else "never")


def _histories(cfg, p, full):
    from repro.core.experiment import run_algorithm
    return {a: run_algorithm(a, cfg, non_iid_p=p, rounds=cfg.rounds,
                             spread=SPREAD, time_budget=time_budget(full))
            for a in ALGOS}


def bench_fig2_3(rows, full):
    """IID convergence + completion time to target accuracy (Figs. 2-3)."""
    cfg = base_cfg(full)
    hs = _histories(cfg, 0.1, full)                # p=0.1 == IID (paper)
    target = 0.97 * max(h.final_accuracy for h in hs.values())
    for a, h in hs.items():
        emit(rows, "fig2", f"final_acc[{a}]", round(h.final_accuracy, 4))
        t = h.completion_time(target)
        emit(rows, "fig3", f"time_to_{target:.2f}[{a}]",
             round(t, 1) if t else "never")
    t_f, t_d = (hs["fedhp"].completion_time(target),
                hs["dpsgd"].completion_time(target))
    if t_f and t_d:
        emit(rows, "fig3", "fedhp_vs_dpsgd_speedup", round(t_d / t_f, 2))
    bench_fig7(rows, hs)                            # waiting time: same runs


def bench_fig4_5(rows, full):
    """Non-IID convergence at p=0.6 and p=0.8 (Figs. 4-5)."""
    cfg = base_cfg(full)
    for p in ((0.6, 0.8) if full else (0.8,)):
        hs = _histories(cfg, p, full)
        for a, h in hs.items():
            emit(rows, "fig4_5", f"acc@p={p}[{a}]",
                 round(h.final_accuracy, 4))


def bench_fig6(rows, full):
    """Accuracy vs non-IID level (Fig. 6)."""
    cfg = base_cfg(full)
    levels = (0.1, 0.4) if not full else (0.1, 0.2, 0.4, 0.6, 0.8)
    for p in levels:
        hs = _histories(cfg, p, full)
        for a, h in hs.items():
            emit(rows, "fig6", f"acc@p={p}[{a}]",
                 round(h.final_accuracy, 4))


def bench_fig7(rows, hs):
    """Average waiting time (Fig. 7) — computed from the fig2 runs."""
    for a, h in hs.items():
        emit(rows, "fig7", f"avg_wait[{a}]", round(h.avg_waiting, 3))


def bench_churn(rows, full):
    """Dynamic membership (churn): completion time to a target accuracy for
    FedHP vs D-PSGD / AD-PSGD while 10-30% of the fleet joins/leaves/
    crashes/straggles on a seeded ChurnSchedule."""
    from repro.core.experiment import churn_from_config, run_algorithm
    cfg = base_cfg(full)
    target = 0.85
    for rate in ((0.1, 0.3) if full else (0.3,)):
        c = replace(cfg, churn_rate=rate)
        sched = churn_from_config(c)
        emit(rows, "churn", f"events@{rate}", len(sched.events))
        for a in ("fedhp", "dpsgd", "adpsgd"):
            h = run_algorithm(a, c, non_iid_p=0.4, rounds=cfg.rounds,
                              spread=SPREAD, churn=sched,
                              time_budget=time_budget(full))
            emit(rows, "churn", f"acc@{rate}[{a}]",
                 round(h.final_accuracy, 4))
            t = h.completion_time(target)
            emit(rows, "churn", f"time_to_{target}@{rate}[{a}]",
                 round(t, 1) if t else "never")


def bench_kernels(rows, full):
    """Pallas kernels vs jnp oracle, us/call (interpret mode on CPU —
    correctness substrate; TPU is the perf target)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    n = 2 ** 17
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    u = jax.random.normal(jax.random.PRNGKey(1), (4, n))
    w = jnp.full((4,), 0.2)

    def timeit(f):
        f()                                    # compile
        t0 = time.perf_counter()
        for _ in range(3):
            r = f()
        jax.tree.leaves(r)[0].block_until_ready()
        return (time.perf_counter() - t0) / 3 * 1e6

    emit(rows, "kernels", "gossip_mix_us",
         round(timeit(lambda: ops.gossip_mix(x, u, w))))
    emit(rows, "kernels", "consensus_dist_us",
         round(timeit(lambda: ops.consensus_dist(x, u))))
    emit(rows, "kernels", "quantize_us",
         round(timeit(lambda: ops.quantize(x))))
    ref = jnp.tensordot(w, u - x[None], axes=1) + x
    got = ops.gossip_mix(x, u, w)
    emit(rows, "kernels", "gossip_max_err",
         float(jnp.max(jnp.abs(ref - got))))


def bench_fused(rows, full):
    """Scan-based fused engine (core/fused.py) vs the reference round loop
    on the D-PSGD smoke shape: identical work, rounds/sec compared.

    Timed on the second run each (first run pays jit compilation for both
    engines); fresh cluster/strategy per run so RNG streams match, but
    data synthesis/sharding stays OUTSIDE the timer — only the engine
    loop is measured. In --smoke mode a speedup < 1 marks the whole
    benchmark run failed."""
    from repro.core import engine
    from repro.core.experiment import setup_experiment
    from repro.core.algorithms import make_strategy
    from repro.core.fused import run_dfl_fused
    from repro.core.topology import make_base_topology
    from repro.simulation.cluster import SimCluster

    cfg = base_cfg(full)
    rounds = 20 if SMOKE else (40 if not full else 80)
    if SMOKE:
        cfg = replace(cfg, num_workers=8)
    cfg = replace(cfg, algorithm="dpsgd")
    train, tx, ty, shards, cluster0 = setup_experiment(
        cfg, non_iid_p=0.4, spread=SPREAD, rounds=rounds)
    base = make_base_topology(cfg.num_workers, cfg.base_topology, cfg.seed)

    def timed(fused):
        # stateful inputs rebuilt per run so the RNG streams restart
        cluster = SimCluster(cfg.num_workers, model_bits=cluster0.model_bits,
                             seed=cfg.seed)
        strategy = make_strategy(cfg, base)
        fn = run_dfl_fused if fused else engine.run_dfl
        t0 = time.perf_counter()
        h = fn(train, tx, ty, shards, cluster, cfg, strategy, rounds=rounds)
        return time.perf_counter() - t0, h

    for fused in (False, True):               # warm the jit caches
        timed(fused)
    t_ref, h_ref = timed(False)
    t_fus, h_fus = timed(True)
    assert len(h_ref.records) == len(h_fus.records)
    emit(rows, "fused", "ref_rounds_per_s", round(rounds / t_ref, 2))
    emit(rows, "fused", "fused_rounds_per_s", round(rounds / t_fus, 2))
    speedup = t_ref / t_fus
    emit(rows, "fused", "speedup", round(speedup, 2))
    emit(rows, "fused", "final_acc_drift",
         round(abs(h_ref.final_accuracy - h_fus.final_accuracy), 6))
    if SMOKE and speedup < 1.0:
        FAILURES.append(f"fused engine slower than reference "
                        f"({speedup:.2f}x)")


def bench_compressed(rows, full):
    """Compressed gossip (int8 + error feedback, core/compression.py) vs
    uncompressed on the same shape: wire bits per transfer, final-accuracy
    parity, and the simulated-clock payoff of paying Eq. 10 comm time /
    wire_ratio. Runs on the fused engine (the CI-gated hot path). In
    --smoke mode a wire reduction < 2x or an accuracy drift > 1% vs the
    uncompressed run fails the whole benchmark (exit 1)."""
    from repro.core.compression import FP32_BITS, wire_bits, wire_ratio
    from repro.core.experiment import model_bits_for, run_algorithm

    cfg = base_cfg(full)
    rounds = 30 if SMOKE else (60 if not full else 150)
    if SMOKE:
        cfg = replace(cfg, num_workers=8)
    params = int(model_bits_for(cfg) // FP32_BITS)
    ratio = wire_ratio(params)
    emit(rows, "compressed", "wire_bits[f32]", wire_bits(params, "none"))
    emit(rows, "compressed", "wire_bits[int8]", wire_bits(params, "int8"))
    emit(rows, "compressed", "wire_reduction", round(ratio, 2))

    hs = {}
    for mode, ef in (("none", True), ("int8", True), ("int8_noef", False)):
        c = replace(cfg, compress=mode.split("_")[0], error_feedback=ef)
        hs[mode] = run_algorithm("dpsgd", c, non_iid_p=0.4, rounds=rounds,
                                 spread=SPREAD, fused=True)
        emit(rows, "compressed", f"final_acc[{mode}]",
             round(hs[mode].final_accuracy, 4))
        emit(rows, "compressed", f"sim_time[{mode}]",
             round(hs[mode].records[-1].cumulative_time, 1))
    drift = abs(hs["int8"].final_accuracy - hs["none"].final_accuracy)
    emit(rows, "compressed", "acc_drift_vs_uncompressed", round(drift, 4))
    emit(rows, "compressed", "sim_time_speedup",
         round(hs["none"].records[-1].cumulative_time /
               hs["int8"].records[-1].cumulative_time, 2))
    if SMOKE:
        if ratio < 2.0:
            FAILURES.append(f"compressed wire reduction {ratio:.2f}x < 2x")
        if drift > 0.01:
            FAILURES.append(f"compressed accuracy drift {drift:.4f} > 1%")


def bench_sparse(rows, full):
    """Sparsified gossip (top-k with x̂ tracking, shared-mask rand-k —
    core/compression.py) vs uncompressed on the fused engine: wire bits
    per transfer and final-accuracy parity at a 10% keep fraction. The
    planner/engines charge Eq. 10 comm / wire_ratio (5x top-k, ~10x
    rand-k — rand-k ships no indices). In --smoke mode the run fails
    (exit 1) if top-k saves < 4x wire bits or drifts > 1% final accuracy
    from the uncompressed run."""
    from repro.core.compression import wire_bits, wire_ratio
    from repro.core.experiment import model_bits_for, run_algorithm

    cfg = base_cfg(full)
    rounds = 30 if SMOKE else (60 if not full else 150)
    if SMOKE:
        cfg = replace(cfg, num_workers=8)
    params = int(model_bits_for(cfg) // 32)
    modes = ("none", "topk:0.1", "randk:0.1")
    for mode in modes[1:]:
        emit(rows, "sparse", f"wire_bits[{mode}]", wire_bits(params, mode))
        emit(rows, "sparse", f"wire_reduction[{mode}]",
             round(wire_ratio(params, mode), 2))

    hs = {}
    for mode in modes:
        c = replace(cfg, compress=mode)
        hs[mode] = run_algorithm("dpsgd", c, non_iid_p=0.4, rounds=rounds,
                                 spread=SPREAD, fused=True)
        emit(rows, "sparse", f"final_acc[{mode}]",
             round(hs[mode].final_accuracy, 4))
        emit(rows, "sparse", f"sim_time[{mode}]",
             round(hs[mode].records[-1].cumulative_time, 1))
    for mode in modes[1:]:
        emit(rows, "sparse", f"acc_drift[{mode}]",
             round(abs(hs[mode].final_accuracy
                       - hs["none"].final_accuracy), 4))
    if SMOKE:
        ratio = wire_ratio(params, "topk:0.1")
        drift = abs(hs["topk:0.1"].final_accuracy
                    - hs["none"].final_accuracy)
        if ratio < 4.0:
            FAILURES.append(f"top-k wire reduction {ratio:.2f}x < 4x")
        if drift > 0.01:
            FAILURES.append(f"top-k accuracy drift {drift:.4f} > 1%")


def bench_sparse_gossip(rows, full):
    """Edge-list gossip (cfg.gossip="sparse") vs the dense [W, W] mixing
    matrix: (1) small-W accuracy parity on the fused engine — the two
    representations must agree to <= 0.1% final accuracy; (2) a large-W
    scaling leg the dense path cannot reach — the dense fused engine
    materializes O(W^2 P) neighbor buffers (122 TB at W=2048 on the
    smoke model), while the sparse engine runs O(E P) through the
    gather-mix-scatter kernel. In --smoke mode the run fails (exit 1)
    if parity drifts > 0.1%, the W=2048 ring exceeds the per-round
    wall-clock budget, or peak RSS exceeds the memory budget."""
    import resource

    from repro.core import topology as topo
    from repro.core.experiment import run_algorithm

    # ---- small-W parity: dense vs sparse fused ---------------------------
    cfg = base_cfg(full)
    rounds = 30 if SMOKE else (60 if not full else 150)
    if SMOKE:
        cfg = replace(cfg, num_workers=8)
    cfg = replace(cfg, base_topology="ring")
    hs = {}
    for gossip in ("dense", "sparse"):
        c = replace(cfg, gossip=gossip)
        hs[gossip] = run_algorithm("dpsgd", c, non_iid_p=0.4, rounds=rounds,
                                   spread=SPREAD, fused=True)
        emit(rows, "sparse_gossip", f"final_acc[{gossip}]",
             round(hs[gossip].final_accuracy, 4))
    drift = abs(hs["sparse"].final_accuracy - hs["dense"].final_accuracy)
    emit(rows, "sparse_gossip", "acc_drift_sparse_vs_dense",
         round(drift, 5))

    # ---- large-W scaling: W where dense is out of reach ------------------
    big_w = 2048 if (SMOKE or full) else 512
    big_rounds = 3
    big = FedHPConfig(num_workers=big_w, rounds=big_rounds, tau_init=2,
                      tau_max=4, lr=0.1, batch_size=16, seed=5,
                      base_topology="ring", gossip="sparse")
    t0 = time.perf_counter()
    h_big = run_algorithm("dpsgd", big, non_iid_p=0.1, rounds=big_rounds,
                          fused=True, num_samples=32 * big_w)
    wall = time.perf_counter() - t0
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    edges = topo.edges_from_adj(topo.ring_topology(big_w)).shape[0]
    emit(rows, "sparse_gossip", "big_w", big_w)
    emit(rows, "sparse_gossip", "big_edges", edges)
    emit(rows, "sparse_gossip", "big_seconds_per_round",
         round(wall / big_rounds, 2))
    emit(rows, "sparse_gossip", "big_peak_rss_mb", round(rss_mb, 0))
    emit(rows, "sparse_gossip", "big_final_acc",
         round(h_big.final_accuracy, 4))
    # the dense fused path at this W would vmap a [W, R, C] neighbor
    # buffer per worker: O(W^2 P) f32 — emit the would-be footprint
    from repro.core import modelspec
    params = modelspec.get_adapter("mlp").param_count  # smoke MLP flat size
    emit(rows, "sparse_gossip", "dense_neighbor_buffer_gb",
         round(big_w * big_w * params * 4 / 2**30, 0))

    if SMOKE:
        if drift > 1e-3:
            FAILURES.append(
                f"sparse gossip accuracy drift {drift:.4f} > 0.1%")
        if wall / big_rounds > 60.0:
            FAILURES.append(
                f"sparse gossip W={big_w} at {wall / big_rounds:.1f}"
                " s/round > 60 s budget")
        if rss_mb > 6144:
            FAILURES.append(
                f"sparse gossip W={big_w} peak RSS {rss_mb:.0f} MB "
                "> 6 GB budget")
        if h_big.final_accuracy < 0.5:
            FAILURES.append(
                f"sparse gossip W={big_w} failed to learn "
                f"(acc {h_big.final_accuracy:.3f})")


def bench_adpsgd(rows, full):
    """Fused event-driven AD-PSGD (core/fused.run_adpsgd_fused) vs the
    reference event loop on the smoke shape: identical event schedule
    (engine.adpsgd_schedule), events/sec compared, min-of-3 timings per
    engine (the loop is host-dispatch bound, so wall-clock noise hits the
    reference hardest). In --smoke mode a speedup < 5x or any final-
    accuracy drift marks the whole benchmark run failed."""
    from repro.core import engine
    from repro.core.experiment import setup_experiment
    from repro.core.fused import run_adpsgd_fused
    from repro.simulation.cluster import SimCluster

    cfg = base_cfg(full)
    rounds = 20 if SMOKE else (40 if not full else 80)
    if SMOKE:
        # tiny cluster AND a small tau: the smoke gate measures the
        # dispatch-overhead elimination (the sequential tau-step grad
        # chain is identical device work in both engines and only
        # dilutes the ratio); the non-smoke leg keeps the compute-heavy
        # shape
        cfg = replace(cfg, num_workers=8, tau_init=2)
    cfg = replace(cfg, algorithm="adpsgd")
    train, tx, ty, shards, cluster0 = setup_experiment(
        cfg, non_iid_p=0.4, spread=SPREAD, rounds=rounds)
    n_events = rounds * cfg.num_workers

    def timed(fused):
        cluster = SimCluster(cfg.num_workers, model_bits=cluster0.model_bits,
                             seed=cfg.seed)
        fn = run_adpsgd_fused if fused else engine.run_adpsgd
        t0 = time.perf_counter()
        h = fn(train, tx, ty, shards, cluster, cfg, rounds=rounds)
        return time.perf_counter() - t0, h

    for fused in (False, True):               # warm the jit caches
        timed(fused)
    t_ref, h_ref = min((timed(False) for _ in range(3)),
                       key=lambda th: th[0])
    t_fus, h_fus = min((timed(True) for _ in range(3)),
                       key=lambda th: th[0])
    assert len(h_ref.records) == len(h_fus.records)
    emit(rows, "adpsgd", "ref_events_per_s", round(n_events / t_ref, 1))
    emit(rows, "adpsgd", "fused_events_per_s", round(n_events / t_fus, 1))
    speedup = t_ref / t_fus
    emit(rows, "adpsgd", "speedup", round(speedup, 2))
    drift = abs(h_ref.final_accuracy - h_fus.final_accuracy)
    emit(rows, "adpsgd", "final_acc_drift", round(drift, 6))
    emit(rows, "adpsgd", "mean_staleness",
         round(float(np.mean([r.staleness for r in h_fus.records])), 3))
    if SMOKE and speedup < 5.0:
        FAILURES.append(f"fused AD-PSGD below the 5x events/sec gate "
                        f"({speedup:.2f}x)")
    if SMOKE and drift > 1e-5:
        FAILURES.append(f"fused AD-PSGD accuracy drifted {drift:.2e} "
                        f"from the reference event loop")


def bench_sharded(rows, full):
    """Sharded [W, P] execution (``run_algorithm(mesh=...)``) vs the
    single-device oracle: (1) a small-W parity leg — the sharded fused
    engine must match the unsharded run to <= 0.1% final accuracy (the
    two paths differ only by the routed delta's summation order);
    (2) a W=4096 ring sparse-gossip leg run ONLY sharded, recording
    rounds/sec and peak RSS, with the per-round trajectory persisted to
    ``BENCH_sharded.json`` (the CI artifact). The large leg also checks
    the point of sharding: every final-params leaf must keep one shard
    per device, so the bytes addressed by a single device stay strictly
    below the whole-array footprint. Needs >= 2 devices (CI exports
    XLA_FLAGS=--xla_force_host_platform_device_count=8); on one device
    the bench emits a skip row (fatal in --smoke mode, where the lane
    guarantees the devices)."""
    import json
    import resource

    import jax

    from repro.core.experiment import run_algorithm
    from repro.launch.mesh import make_worker_mesh

    ndev = jax.device_count()
    if ndev < 2:
        emit(rows, "sharded", "skipped[devices]", ndev)
        if SMOKE:
            FAILURES.append(
                "sharded bench needs >= 2 devices (export XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)")
        return
    n_shards = 4 if ndev >= 4 else 2
    mesh = make_worker_mesh(n_shards)
    emit(rows, "sharded", "n_shards", n_shards)

    # ---- small-W parity: sharded fused vs single-device fused ------------
    cfg = base_cfg(full)
    rounds = 30 if SMOKE else (60 if not full else 150)
    if SMOKE:
        cfg = replace(cfg, num_workers=8)
    cfg = replace(cfg, base_topology="ring", gossip="sparse")
    hs = {}
    for leg, m in (("oracle", None), ("sharded", mesh)):
        hs[leg] = run_algorithm("dpsgd", cfg, non_iid_p=0.4, rounds=rounds,
                                spread=SPREAD, fused=True, mesh=m)
        emit(rows, "sharded", f"final_acc[{leg}]",
             round(hs[leg].final_accuracy, 4))
    drift = abs(hs["sharded"].final_accuracy - hs["oracle"].final_accuracy)
    emit(rows, "sharded", "acc_drift_vs_oracle", round(drift, 5))

    # ---- large-W scaling: W=4096 sparse ring, sharded only ---------------
    big_w = 4096 if (SMOKE or full) else 1024
    big_rounds = 3
    big = FedHPConfig(num_workers=big_w, rounds=big_rounds, tau_init=2,
                      tau_max=4, lr=0.1, batch_size=16, seed=5,
                      base_topology="ring", gossip="sparse")
    t0 = time.perf_counter()
    h_big = run_algorithm("dpsgd", big, non_iid_p=0.1, rounds=big_rounds,
                          fused=True, mesh=mesh, num_samples=32 * big_w)
    wall = time.perf_counter() - t0
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    emit(rows, "sharded", "big_w", big_w)
    emit(rows, "sharded", "big_rounds_per_s",
         round(big_rounds / wall, 3))
    emit(rows, "sharded", "big_peak_rss_mb", round(rss_mb, 0))
    emit(rows, "sharded", "big_final_acc", round(h_big.final_accuracy, 4))

    # big_w divides n_shards, so unpad is an identity and the final
    # params stay sharded: each device must address a strict subset
    leaf = jax.tree.leaves(h_big.final_params)[0]
    shard_bytes = max(s.data.nbytes for s in leaf.addressable_shards)
    emit(rows, "sharded", "big_param_bytes", leaf.nbytes)
    emit(rows, "sharded", "big_per_shard_bytes", shard_bytes)

    a = h_big.as_arrays()
    with open("BENCH_sharded.json", "w") as f:
        json.dump({"mode": "smoke" if SMOKE else
                   ("full" if full else "quick"),
                   "n_shards": n_shards, "small_w": cfg.num_workers,
                   "small_drift": drift, "big_w": big_w,
                   "big_rounds_per_s": round(big_rounds / wall, 3),
                   "big_peak_rss_mb": round(rss_mb, 0),
                   "per_shard_bytes": shard_bytes,
                   "param_bytes": leaf.nbytes,
                   "trajectory": {k: a[k].tolist() for k in
                                  ("round", "accuracy", "loss", "consensus",
                                   "cumulative_time")}}, f)
    emit(rows, "sharded", "trajectory_file", "BENCH_sharded.json")

    if SMOKE:
        if drift > 1e-3:
            FAILURES.append(
                f"sharded accuracy drift {drift:.4f} > 0.1% vs the "
                "single-device oracle")
        if shard_bytes >= leaf.nbytes:
            FAILURES.append(
                f"sharded W={big_w} params not actually sharded: one "
                f"device addresses {shard_bytes} of {leaf.nbytes} bytes")
        if wall / big_rounds > 120.0:
            FAILURES.append(
                f"sharded W={big_w} at {wall / big_rounds:.1f} s/round "
                "> 120 s budget")
        if h_big.final_accuracy < 0.5:
            FAILURES.append(
                f"sharded W={big_w} failed to learn "
                f"(acc {h_big.final_accuracy:.3f})")


def bench_scenarios(rows, full):
    """Scenario-diversity benchmark: (1) FedHP's adaptive topology vs
    fixed complex-network graphs (BA / WS / geo) under correlated rack
    outages (``ChurnSchedule.generate_correlated``); (2) Byzantine
    sign-flip attackers vs trimmed-mean robust gossip (core/robust.py);
    (3) time-varying non-IID drift (``cfg.drift_every``). Per-leg final
    metrics are emitted as CSV rows and the full per-round trajectories
    are persisted to ``BENCH_scenarios.json`` (the CI artifact).

    In --smoke mode the Byzantine legs are gated: with 20% sign-flip
    attackers, trimmed-mean gossip must reach >= 90% of the clean run's
    final accuracy through BOTH the reference engine and the fused
    scan's gather-sort-trim kernel, plain uniform mixing must degrade
    measurably below clean, and AD-PSGD accept/reject screening
    (robust="screen:<z>") must recover >= 85% of its clean run — any
    failure exits 1."""
    import json

    from repro.core.experiment import run_algorithm
    from repro.simulation.cluster import ChurnSchedule

    cfg = base_cfg(full)
    rounds = 40 if SMOKE else (60 if not full else 150)
    if SMOKE:
        cfg = replace(cfg, num_workers=16)
    n = cfg.num_workers
    traj: dict[str, dict] = {}

    def record(leg, h):
        a = h.as_arrays()
        traj[leg] = {
            "final_accuracy": round(h.final_accuracy, 4),
            "trajectory": {k: a[k].tolist() for k in
                           ("round", "accuracy", "loss", "consensus",
                            "cumulative_time")},
        }

    # ---- (1) adaptive vs fixed complex-network graphs under outages ------
    racks = 4
    outages = ChurnSchedule.generate_correlated(
        n, rounds, racks=racks, outages=2, seed=cfg.churn_seed,
        min_alive=cfg.churn_min_alive)
    emit(rows, "scenarios", "outage_events", len(outages.events))
    # "base" = fixed given topology at tau_init (dpsgd always plans a
    # ring, so it can't exercise the complex-network graphs)
    topo_legs = [("fedhp", "full"), ("base", "ba:2"),
                 ("base", "ws:4:0.2"), ("base", f"geo:{racks}")]
    for algo, base in topo_legs:
        c = replace(cfg, base_topology=base)
        h = run_algorithm(algo, c, non_iid_p=0.4, rounds=rounds,
                          spread=SPREAD, churn=outages, fused=True)
        leg = f"outage[{algo}@{base}]"
        emit(rows, "scenarios", f"acc_{leg}", round(h.final_accuracy, 4))
        record(leg, h)

    # ---- (2) Byzantine fraction: clean vs plain vs trimmed ---------------
    nb = 10 if SMOKE else n            # 20% attackers on the gate shape
    byz = tuple(range(0, nb, 5))       # workers 0, 5, ... -> nb/5 = 20%
    byz_rounds = 30 if SMOKE else rounds
    bcfg = replace(cfg, num_workers=nb, tau_init=4,
                   byzantine_attack="signflip")
    trimmed_cfg = replace(bcfg, byzantine=byz,
                          robust=f"trimmed:{len(byz)}")
    legs = {"clean": (replace(bcfg, byzantine=(), robust="none"), False),
            "byz_plain": (replace(bcfg, byzantine=byz, robust="none"),
                          False),
            "byz_trimmed": (trimmed_cfg, False),
            # the LOWERED path: trimmed-mean through the fused scan's
            # gather-sort-trim kernel, not the reference mix
            "byz_trimmed_fused": (trimmed_cfg, True)}
    accs = {}
    for name, (c, fus) in legs.items():
        h = run_algorithm("dpsgd", c, non_iid_p=0.4, rounds=byz_rounds,
                          spread=SPREAD, fused=fus)
        accs[name] = h.final_accuracy
        emit(rows, "scenarios", f"acc_byz[{name}]",
             round(h.final_accuracy, 4))
        record(f"byz[{name}]", h)
    emit(rows, "scenarios", "byz_fraction", round(len(byz) / nb, 2))
    emit(rows, "scenarios", "trimmed_recovery",
         round(accs["byz_trimmed"] / max(accs["clean"], 1e-9), 3))
    emit(rows, "scenarios", "trimmed_fused_recovery",
         round(accs["byz_trimmed_fused"] / max(accs["clean"], 1e-9), 3))

    # ---- (2b) AD-PSGD lying wire: clean vs plain vs screened -------------
    # same 20% sign-flip fleet through the event-driven engine; the
    # defense is per-event accept/reject screening (robust="screen:<z>")
    # rather than a trim window (a pairwise exchange has only 2 samples)
    alegs = {"adpsgd_clean": replace(bcfg, byzantine=(), robust="none"),
             "adpsgd_byz": replace(bcfg, byzantine=byz, robust="none"),
             "adpsgd_screen": replace(bcfg, byzantine=byz,
                                      robust="screen:8")}
    for name, c in alegs.items():
        h = run_algorithm("adpsgd", c, non_iid_p=0.4, rounds=byz_rounds,
                          spread=SPREAD, fused=True)
        accs[name] = h.final_accuracy
        emit(rows, "scenarios", f"acc_byz[{name}]",
             round(h.final_accuracy, 4))
        record(f"byz[{name}]", h)
        if h.screen_rejects is not None:
            emit(rows, "scenarios", "screen_rejects",
                 int(sum(h.screen_rejects)))
    emit(rows, "scenarios", "screen_recovery",
         round(accs["adpsgd_screen"] / max(accs["adpsgd_clean"], 1e-9),
               3))

    # ---- (3) time-varying non-IID drift ----------------------------------
    for name, c in (("static", cfg),
                    ("drift", replace(cfg, drift_every=max(rounds // 8,
                                                           1)))):
        h = run_algorithm("dpsgd", c, non_iid_p=0.6, rounds=rounds,
                          spread=SPREAD, fused=True)
        emit(rows, "scenarios", f"acc_drift[{name}]",
             round(h.final_accuracy, 4))
        record(f"drift[{name}]", h)

    with open("BENCH_scenarios.json", "w") as f:
        json.dump({"mode": "smoke" if SMOKE else
                   ("full" if full else "quick"),
                   "workers": n, "rounds": rounds, "legs": traj}, f)
    emit(rows, "scenarios", "trajectory_file", "BENCH_scenarios.json")

    if SMOKE:
        if accs["byz_trimmed"] < 0.9 * accs["clean"]:
            FAILURES.append(
                f"trimmed-mean gossip under 20% sign-flip attackers "
                f"reached {accs['byz_trimmed']:.3f} < 90% of clean "
                f"({accs['clean']:.3f})")
        if accs["clean"] - accs["byz_plain"] < 0.02:
            FAILURES.append(
                f"plain uniform mixing under attack should degrade "
                f"measurably; clean {accs['clean']:.3f} vs attacked "
                f"{accs['byz_plain']:.3f}")
        if accs["byz_trimmed_fused"] < 0.9 * accs["clean"]:
            FAILURES.append(
                f"FUSED trimmed-mean gossip under 20% sign-flip "
                f"attackers reached {accs['byz_trimmed_fused']:.3f} "
                f"< 90% of clean ({accs['clean']:.3f})")
        if accs["adpsgd_screen"] < 0.85 * accs["adpsgd_clean"]:
            FAILURES.append(
                f"AD-PSGD screening under 20% sign-flip attackers "
                f"reached {accs['adpsgd_screen']:.3f} < 85% of clean "
                f"({accs['adpsgd_clean']:.3f})")


def bench_collective(rows, full):
    """Adapted-topology gossip vs all-reduce wire bytes (the roofline knob
    the paper's technique controls; DESIGN.md §3)."""
    from repro.core import topology as topo
    n, params = 32, 1.0                       # per-model payload = 1 unit
    full_t = topo.full_topology(n)
    ring = topo.ring_topology(n)
    for name, adj in (("full", full_t), ("ring", ring)):
        m = len(topo.matching_decomposition(adj))
        emit(rows, "collective", f"matchings[{name}]", m)
        emit(rows, "collective", f"gossip_bytes[{name}]", m * params)
    emit(rows, "collective", "allreduce_bytes",
         round(2 * (n - 1) / n * params, 3))


def bench_pytree(rows, full):
    """Registry pytree models through the DFL engines (core/modelspec.py):
    a tiny dense transformer LM trains under fedhp on BOTH the reference
    engine (core/engine.run_dfl) and the fused scan (run_dfl_fused), with
    a per-leaf codec map ("leafmap:embed=randk:...,ln=none,default=int8")
    compiled against the adapter's leaf-offset table. Emits the exact
    wire accounting of the leaf map vs uniform int8 and persists both
    trajectories to ``BENCH_pytree.json`` (the CI artifact).

    In --smoke mode the run fails (exit 1) if reference and fused final
    accuracy drift > 0.1% (the leafmap gossip payload is shared oracle
    math — the engines must agree), if the leaf map's wire reduction
    falls below uniform int8's, or if the LM's inverse perplexity does
    not improve >= 5% over the run (the smoke horizon is too short to
    cross the uniform-entropy floor; steady descent is the learning
    gate)."""
    import json

    from repro.core import compression, modelspec
    from repro.core.experiment import run_algorithm

    cfg = base_cfg(full)
    rounds = 10 if SMOKE else (20 if not full else 40)
    # transformer LM under plain SGD: smaller cluster than the MLP
    # smoke shape, and a leaf-mapped codec on the gossip wire. The
    # smoke model is deliberately tiny — fedhp replans every round, so
    # each distinct (adj, tau_cap) pair costs one scan compile of the
    # whole transformer
    model = ("dense:d=16,layers=1,ff=32,vocab=32,seq=8" if SMOKE
             else "dense")
    leafmap = "leafmap:embed=randk:0.05,ln=none,default=int8"
    cfg = replace(cfg, num_workers=6 if SMOKE else 8, tau_init=6,
                  tau_max=12, lr=0.25 if SMOKE else 0.05, model=model,
                  compress=leafmap)

    adapter = modelspec.get_adapter(cfg.model)
    lcodec = compression.parse_mode(leafmap).compile(adapter.leaf_offsets())
    int8_ratio = compression.wire_ratio(adapter.param_count, "int8")
    leaf_ratio = lcodec.wire_ratio()
    emit(rows, "pytree", "param_count", adapter.param_count)
    emit(rows, "pytree", "model_bits", int(adapter.model_bits))
    emit(rows, "pytree", "wire_reduction[int8]", round(int8_ratio, 2))
    emit(rows, "pytree", "wire_reduction[leafmap]", round(leaf_ratio, 2))
    emit(rows, "pytree", "leaf_segments", len(lcodec.segments))

    traj: dict[str, dict] = {}
    hs = {}
    for leg, fused in (("ref", False), ("fused", True)):
        h = run_algorithm("fedhp", cfg, non_iid_p=0.4, rounds=rounds,
                          spread=SPREAD, fused=fused)
        hs[leg] = h
        a = h.as_arrays()
        traj[leg] = {
            "final_accuracy": round(h.final_accuracy, 6),
            "trajectory": {k: a[k].tolist() for k in
                           ("round", "accuracy", "loss", "consensus",
                            "cumulative_time")},
        }
        emit(rows, "pytree", f"final_acc[{leg}]",
             round(h.final_accuracy, 4))
    drift = abs(hs["ref"].final_accuracy - hs["fused"].final_accuracy)
    emit(rows, "pytree", "acc_drift_ref_vs_fused", round(drift, 6))

    with open("BENCH_pytree.json", "w") as f:
        json.dump({"mode": "smoke" if SMOKE else
                   ("full" if full else "quick"),
                   "model": adapter.spec, "workers": cfg.num_workers,
                   "rounds": rounds, "compress": leafmap,
                   "param_count": adapter.param_count,
                   "wire_reduction": {"int8": int8_ratio,
                                      "leafmap": leaf_ratio},
                   "legs": traj}, f)
    emit(rows, "pytree", "trajectory_file", "BENCH_pytree.json")

    if SMOKE:
        if drift > 1e-3:
            FAILURES.append(
                f"pytree ref-vs-fused accuracy drift {drift:.5f} > 0.1%")
        if leaf_ratio < int8_ratio:
            FAILURES.append(
                f"leafmap wire reduction {leaf_ratio:.2f}x below uniform "
                f"int8 ({int8_ratio:.2f}x) — the per-leaf map should "
                "never pay more than its default codec alone")
        acc0 = hs["fused"].records[0].accuracy
        if hs["fused"].final_accuracy < 1.05 * acc0:
            FAILURES.append(
                f"pytree LM failed the 5% learning gate "
                f"({acc0:.4f} -> {hs['fused'].final_accuracy:.4f})")


BENCHES = {
    "fig1": bench_fig1,
    "fig2_3": bench_fig2_3,
    "fig4_5": bench_fig4_5,
    "fig6": bench_fig6,
    "churn": bench_churn,
    "kernels": bench_kernels,
    "collective": bench_collective,
    "fused": bench_fused,
    "compressed": bench_compressed,
    "sparse": bench_sparse,
    "sparse_gossip": bench_sparse_gossip,
    "sharded": bench_sharded,
    "adpsgd": bench_adpsgd,
    "scenarios": bench_scenarios,
    "pytree": bench_pytree,
}

SMOKE = False              # set by --smoke; bench_fused reads it
FAILURES: list[str] = []   # regressions collected during the run


def main(argv=None) -> int:
    global SMOKE
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper scale: 30 workers, full rounds")
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: tiny cluster, perf regressions fatal")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    SMOKE = args.smoke
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    rows: list = []
    print("benchmark,metric,value")
    todo = [args.only] if args.only else list(BENCHES)
    t0 = time.time()
    for name in todo:
        BENCHES[name](rows, args.full)
    print(f"\n# {len(rows)} metrics in {time.time() - t0:.0f}s "
          f"({'full' if args.full else 'quick'} mode)")
    for f in FAILURES:
        print(f"# FAIL: {f}")
    return 1 if FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())

"""Reproduce the paper's heterogeneity story end to end (Sec. V):

sweeps the non-IID level p and reports, per algorithm, accuracy /
completion time / average waiting time — the compact version of
Figs. 2-7 — plus a fault-injection leg (two workers die mid-run).

    PYTHONPATH=src python examples/heterogeneity_study.py

``--churn`` runs the dynamic-membership scenario instead: a seeded
ChurnSchedule (joins, graceful leaves, crashes, straggler spikes) hits
10% and 30% of the fleet and the engines race to a target accuracy —
FedHP's adaptive topology + tau re-equalization vs the static baselines.

    PYTHONPATH=src python examples/heterogeneity_study.py --churn

``--fused`` routes every algorithm through the scan-based fused engines
(core/fused.py: run_dfl_fused for the synchronous strategies,
run_adpsgd_fused for AD-PSGD) — same trajectories, one device dispatch
per segment instead of ~10 per round / ~3 per event:

    PYTHONPATH=src python examples/heterogeneity_study.py --fused

``--adpsgd`` runs the asynchronous study instead: AD-PSGD on the
reference event loop vs the fused event scan, uncompressed vs int8
compensated pairwise exchange, with per-round staleness reported:

    PYTHONPATH=src python examples/heterogeneity_study.py --adpsgd

``--compressed`` runs the compressed-gossip comparison instead: FedHP
and D-PSGD with int8 + error-feedback gossip (core/compression.py,
~3.6x fewer wire bytes, Eq. 10 comm time / wire_ratio) against their
uncompressed selves, racing to a target accuracy on equal wall time:

    PYTHONPATH=src python examples/heterogeneity_study.py --compressed

``--pytree`` runs the registry-model study instead: tiny dense
transformer / xLSTM language models (models/registry.py behind
core/modelspec.py's ModelAdapter) train under fedhp with a per-leaf
codec map ("leafmap:embed=randk:0.05,ln=none,default=int8") on the
gossip wire, under 10% churn:

    PYTHONPATH=src python examples/heterogeneity_study.py --pytree

``--sharded`` runs the sharded-execution study instead: the [W, P]
worker matrix split across the host's devices over a worker mesh
(runtime/shardexec), sharded vs single-device trajectories side by
side — force a multi-device CPU first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/heterogeneity_study.py --sharded

``--scenarios`` runs the scenario-axis study instead: FedHP's adaptive
topology vs fixed complex-network graphs (Barabási–Albert,
Watts–Strogatz, geo/racks) under correlated rack outages, then 20%
sign-flip Byzantine workers with plain vs trimmed-mean vs median gossip:

    PYTHONPATH=src python examples/heterogeneity_study.py --scenarios
"""
import argparse
from dataclasses import replace

from repro.configs.base import FedHPConfig
from repro.core.experiment import churn_from_config, run_algorithm
from repro.launch.cache import enable_compile_cache

CFG = FedHPConfig(num_workers=10, rounds=100, tau_init=8, tau_max=30,
                  lr=0.15, lr_decay=0.993, batch_size=32, seed=7)
BUDGET = 60.0
TARGET_ACC = 0.85
CHURN_ALGOS = ("fedhp", "dpsgd", "adpsgd")


def heterogeneity_study(fused: bool = False):
    print(f"{'algo':8s} {'p':>4s} {'acc':>6s} {'time(s)':>8s} {'wait':>6s}")
    for p in (0.1, 0.8):
        for algo in ("fedhp", "dpsgd", "ldsgd", "pens", "adpsgd"):
            h = run_algorithm(algo, CFG, non_iid_p=p, spread=3.0,
                              time_budget=BUDGET, fused=fused)
            print(f"{algo:8s} {p:4.1f} {h.final_accuracy:6.3f} "
                  f"{h.records[-1].cumulative_time:8.1f} "
                  f"{h.avg_waiting:6.2f}")

    print("\nfault tolerance: workers {0, 3} die at round 5 (FedHP)")
    h = run_algorithm("fedhp", CFG, non_iid_p=0.4, spread=3.0,
                      time_budget=BUDGET, fail_at={5: [0, 3]}, fused=fused)
    print(f"  survived; final accuracy {h.final_accuracy:.3f} "
          f"(topology repaired, Sec. DESIGN §6)")


def churn_study(fused: bool = False):
    """FedHP vs D-PSGD vs AD-PSGD under 10% / 30% dynamic membership."""
    print("dynamic membership: join/leave/crash/straggle schedule, seeded")
    print(f"{'algo':8s} {'churn':>6s} {'acc':>6s} "
          f"{'t_to_{:.0%}'.format(TARGET_ACC):>9s} {'total(s)':>9s} "
          f"{'events':>7s}")
    for rate in (0.1, 0.3):
        cfg = replace(CFG, churn_rate=rate)
        sched = churn_from_config(cfg)
        kinds = ",".join(f"{k}:{sum(e.kind == k for e in sched.events)}"
                         for k in ("leave", "crash", "join", "straggle")
                         if any(e.kind == k for e in sched.events))
        for algo in CHURN_ALGOS:
            h = run_algorithm(algo, cfg, non_iid_p=0.4, spread=3.0,
                              churn=sched, time_budget=BUDGET,
                              fused=fused)
            t = h.completion_time(TARGET_ACC)
            t_str = f"{t:9.1f}" if t is not None else f"{'never':>9s}"
            print(f"{algo:8s} {rate:6.0%} {h.final_accuracy:6.3f} {t_str} "
                  f"{h.records[-1].cumulative_time:9.1f} {kinds:>7s}")


def compressed_study(fused: bool = False):
    """Accuracy vs completion time: int8+EF compressed gossip against
    uncompressed FedHP / D-PSGD on the same simulated-time budget."""
    from repro.core.compression import FP32_BITS, wire_ratio
    from repro.core.experiment import model_bits_for
    ratio = wire_ratio(int(model_bits_for(CFG) // FP32_BITS))
    print(f"compressed gossip: int8 + error feedback, "
          f"{ratio:.2f}x fewer wire bits, comm time / {ratio:.2f}")
    print(f"{'algo':8s} {'wire':>6s} {'acc':>6s} "
          f"{'t_to_{:.0%}'.format(TARGET_ACC):>9s} {'total(s)':>9s}")
    for algo in ("fedhp", "dpsgd"):
        for mode in ("none", "int8"):
            cfg = replace(CFG, compress=mode)
            h = run_algorithm(algo, cfg, non_iid_p=0.4, spread=3.0,
                              time_budget=BUDGET, fused=fused)
            t = h.completion_time(TARGET_ACC)
            t_str = f"{t:9.1f}" if t is not None else f"{'never':>9s}"
            print(f"{algo:8s} {mode:>6s} {h.final_accuracy:6.3f} {t_str} "
                  f"{h.records[-1].cumulative_time:9.1f}")


def scenarios_study(fused: bool = False):
    """Scenario axis: complex-network topologies under correlated rack
    outages, then Byzantine attackers vs robust gossip."""
    from repro.simulation.cluster import ChurnSchedule

    racks = 4
    sched = ChurnSchedule.generate_correlated(
        CFG.num_workers, CFG.rounds, racks=racks, outages=2, seed=CFG.seed)
    n_out = sum(1 for e in sched.events if e.kind == "crash")
    print(f"rack outages: {n_out} grouped crash events over {racks} racks")
    print(f"{'algo':8s} {'topology':>10s} {'acc':>6s} {'total(s)':>9s}")
    for algo, base in (("fedhp", "full"), ("base", "ba:2"),
                       ("base", "ws:4:0.2"), ("base", f"geo:{racks}")):
        cfg = replace(CFG, base_topology=base)
        h = run_algorithm(algo, cfg, non_iid_p=0.4, spread=3.0,
                          churn=sched, time_budget=BUDGET, fused=fused)
        print(f"{algo:8s} {base:>10s} {h.final_accuracy:6.3f} "
              f"{h.records[-1].cumulative_time:9.1f}")

    byz = (3, 7)                                 # 20% of the fleet
    print(f"\nByzantine: workers {byz} sign-flip on the wire "
          f"(reference engine)")
    print(f"{'robust':>10s} {'acc':>6s}")
    for robust in ("none", "trimmed:2", "median"):
        cfg = replace(CFG, rounds=30, byzantine=byz, robust=robust)
        h = run_algorithm("dpsgd", cfg, non_iid_p=0.4, spread=3.0)
        print(f"{robust:>10s} {h.final_accuracy:6.3f}")


def pytree_study(fused: bool = False):
    """Registry pytree models under DFL (core/modelspec.py): a tiny
    dense transformer LM and a tiny xLSTM train under fedhp with a
    per-leaf codec map on the gossip wire, under 10% churn — the
    engines never see the model family, only its ModelAdapter."""
    from repro.core import compression, modelspec

    leafmap = "leafmap:embed=randk:0.05,ln=none,default=int8"
    print("registry pytree models under fedhp + churn "
          "(accuracy = exp(-loss), random-token baseline shown)")
    print(f"{'model':24s} {'params':>7s} {'wire':>6s} {'base':>6s} "
          f"{'acc':>6s} {'total(s)':>9s}")
    # tiny dims: fedhp replans every round and each distinct plan shape
    # costs one jit of the whole transformer/xLSTM
    for model in ("dense:d=16,layers=1,ff=32,vocab=32,seq=8",
                  "xlstm:d=16,ff=32,vocab=32,seq=8"):
        cfg = replace(CFG, model=model, compress=leafmap, lr=0.25,
                      tau_init=6, rounds=25, churn_rate=0.1)
        adapter = modelspec.get_adapter(cfg.model)
        lcodec = compression.parse_mode(leafmap).compile(
            adapter.leaf_offsets())
        h = run_algorithm("fedhp", cfg, non_iid_p=0.4, spread=3.0,
                          time_budget=BUDGET, fused=fused)
        print(f"{model.partition(':')[0]:24s} {adapter.param_count:7d} "
              f"{lcodec.wire_ratio():5.1f}x "
              f"{1.0 / adapter.cfg.vocab_size:6.4f} "
              f"{h.final_accuracy:6.4f} "
              f"{h.records[-1].cumulative_time:9.1f}")


def sharded_study(fused: bool = False):
    """Sharded [W, P] execution: the fleet's worker matrix split across
    the host's devices (one shard_map program per round / segment,
    cross-shard gossip over lax.ppermute) next to the single-device run
    it must reproduce — host clock fields identical, accuracy to
    summation-order drift."""
    import jax

    from repro.launch.mesh import make_worker_mesh

    ndev = jax.device_count()
    if ndev < 2:
        print("sharded study needs a multi-device host; run with\n"
              "  XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    n_shards = 4 if ndev >= 4 else 2
    mesh = make_worker_mesh(n_shards)
    cfg = replace(CFG, rounds=40, gossip="sparse",
                  num_workers=(CFG.num_workers + n_shards - 1)
                  // n_shards * n_shards + 2)   # exercise padding too
    print(f"sharded execution: W={cfg.num_workers} over {n_shards} "
          f"device shards ({ndev} devices visible)")
    print(f"{'algo':8s} {'path':>8s} {'acc':>6s} {'total(s)':>9s} "
          f"{'wait':>6s}")
    for algo in ("fedhp", "dpsgd"):
        for m in (None, mesh):
            h = run_algorithm(algo, cfg, non_iid_p=0.4, spread=3.0,
                              time_budget=BUDGET, fused=fused, mesh=m)
            path = "sharded" if m is not None else "1-dev"
            print(f"{algo:8s} {path:>8s} {h.final_accuracy:6.3f} "
                  f"{h.records[-1].cumulative_time:9.1f} "
                  f"{h.avg_waiting:6.2f}")


def adpsgd_study():
    """Asynchronous engines head to head: reference event loop vs fused
    event scan, uncompressed vs int8 compensated pairwise exchange."""
    print("AD-PSGD: event-driven engines, staleness + compression")
    print(f"{'engine':10s} {'wire':>6s} {'acc':>6s} {'total(s)':>9s} "
          f"{'stale':>6s}")
    for mode in ("none", "int8"):
        cfg = replace(CFG, compress=mode)
        for fused in (False, True):
            h = run_algorithm("adpsgd", cfg, non_iid_p=0.4, spread=3.0,
                              time_budget=BUDGET, fused=fused)
            stale = sum(r.staleness for r in h.records) / len(h.records)
            print(f"{'fused' if fused else 'reference':10s} {mode:>6s} "
                  f"{h.final_accuracy:6.3f} "
                  f"{h.records[-1].cumulative_time:9.1f} {stale:6.2f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--churn", action="store_true",
                    help="run the dynamic-membership (churn) scenario")
    ap.add_argument("--compressed", action="store_true",
                    help="run the compressed-gossip (int8 + EF) scenario")
    ap.add_argument("--adpsgd", action="store_true",
                    help="run the asynchronous (AD-PSGD) engine study "
                         "(always compares reference AND fused engines; "
                         "--fused has no extra effect here)")
    ap.add_argument("--scenarios", action="store_true",
                    help="run the scenario-axis study (complex-network "
                         "topologies, rack outages, Byzantine workers)")
    ap.add_argument("--pytree", action="store_true",
                    help="run registry pytree models (dense / xlstm LMs) "
                         "under fedhp with a per-leaf codec map")
    ap.add_argument("--sharded", action="store_true",
                    help="run the sharded [W, P] study (needs a multi-"
                         "device host; see XLA_FLAGS in the docstring)")
    ap.add_argument("--fused", action="store_true",
                    help="run the algorithms on the fused scan engines")
    args = ap.parse_args()
    enable_compile_cache()
    if args.churn:
        churn_study(fused=args.fused)
    elif args.scenarios:
        scenarios_study(fused=args.fused)
    elif args.compressed:
        compressed_study(fused=args.fused)
    elif args.pytree:
        pytree_study(fused=args.fused)
    elif args.sharded:
        sharded_study(fused=args.fused)
    elif args.adpsgd:
        adpsgd_study()
    else:
        heterogeneity_study(fused=args.fused)


if __name__ == "__main__":
    main()

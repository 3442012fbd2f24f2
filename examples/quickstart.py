"""Quickstart: the public API in ~60 lines.

1. Run a mini FedHP DFL experiment on the simulated heterogeneous edge
   cluster (the paper's setting) and compare with D-PSGD.
2. Instantiate an assigned architecture (reduced config) and take one
   training step.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_smoke_config
from repro.configs.base import FedHPConfig
from repro.core.experiment import run_algorithm
from repro.launch.cache import enable_compile_cache
from repro.models import registry


def dfl_demo():
    print("== FedHP vs D-PSGD on a simulated heterogeneous edge cluster ==")
    cfg = FedHPConfig(num_workers=8, rounds=10, tau_init=5, tau_max=20,
                      lr=0.1, batch_size=32, seed=0)
    for algo in ("fedhp", "dpsgd"):
        h = run_algorithm(algo, cfg, non_iid_p=0.6)
        print(f"  {algo:6s}: accuracy={h.final_accuracy:.3f} "
              f"completion={h.records[-1].cumulative_time:7.1f}s "
              f"avg_waiting={h.avg_waiting:.2f}s")


def model_demo():
    print("== one train step of an assigned arch (reduced config) ==")
    cfg = get_smoke_config("olmoe-1b-7b")           # MoE family
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64,
                                global_batch=2)
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    batch = registry.make_batch(cfg, shape, jax.random.PRNGKey(1))

    @jax.jit
    def step(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda pp: registry.loss_fn(cfg, pp, b), has_aux=True)(p)
        return loss, jax.tree.map(lambda w, gg: w - 0.01 * gg.astype(w.dtype),
                                  p, g)

    loss, params = step(params, batch)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"  arch={cfg.name} params={n:,} loss={float(loss):.3f}")


def main():
    enable_compile_cache()
    dfl_demo()
    model_demo()


if __name__ == "__main__":
    main()

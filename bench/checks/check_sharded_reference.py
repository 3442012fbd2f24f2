"""Self-check of the reference replay over several chips
(``reference/dfl.py``, ``chips > 1``), on the CPU with 4 forced host
devices.

- A tiny dense fleet (W=8, 3 rounds, taus and topologies that vary by
  round, Alg. 1 measured in the first 2) is replayed on one device and
  split over 4: the fleet loss, the consensus distance, every (worker,
  leaf) change and first-gradient norm, and every Alg. 1 reading agree
  within 1e-6 relative.
- The split programs exchange what they should and no more: local SGD,
  the first gradients and the norms run no collective; the mix and the
  pairwise distances gather one leaf of the fleet a program; the
  evaluation only sums across chips.
- With ``chips == 1`` the replay builds the programs it always built:
  their lowered text hashes to the digests below, taken from the
  one-device reference before the split was added.

    python3 bench/checks/check_sharded_reference.py

It relaunches itself with ``XLA_FLAGS=--xla_force_host_platform_device_
count=4`` and ``JAX_PLATFORMS=cpu`` unless both are set.
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]

FLAG = "--xla_force_host_platform_device_count=4"
W, ROUNDS, MEASURED, SEQ, BATCH, TAU_MAX = 8, 3, 2, 8, 4, 3
TOL = 1e-6
# sha256 of each one-device program's lowered text at this check's sizes
ONE_DEVICE = {
    "evaluate":
        "42b0d914c08c38f65cd5e659c079ab53438dfdc251641109f9381482d82457de",
    "first_grads":
        "4cc8f01f0277bf9f9bbfaf564b740f79ba43ed096c1914cf00e9ca579ff289cf",
    "init":
        "93aa2cd917860b69bd14974344d723f968874f6ac3604c0571d190a8a83b569e",
    "local_sgd":
        "ba5d1d8323ca27465c808cf49102894f8d6d3c9ca306997f24f237d444432993",
    "measure_one":
        "e734e5dfa4d96532f9aa05a80e1376168dfaee46e738cc6eabb43d34e96c7d31",
    "mix":
        "b9d4e634c891163dfb8a54a4be6660d392eb5403a48f8d51c31b97b85f1dccee",
    "pairwise":
        "f40ed730f71de9ef5eb868eccbc8d3c3a66a5df2df6222b300476924c2b3f001",
    "rows_norms":
        "6bdda214a19262591839c386c04db3e8f8fe130b57c598e8e80703deae6a3cd1",
}


def relaunch() -> int:
    env = dict(os.environ, XLA_FLAGS=FLAG, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, __file__, *sys.argv[1:]],
                          env=env, cwd=ROOT, timeout=1200)
    return proc.returncode


def tiny_config() -> dict:
    import tiny
    cfg = json.loads((ROOT / "bench" / "configs" / "smollm360m-l4v8.json")
                     .read_text())
    return tiny.tiny_model(cfg)


def experiment(cfg: dict):
    """A fleet's inputs and plans, from one generator: token rows, their
    class labels, W shards, the test rows and (taus, adj) per round."""
    import numpy as np
    rng = np.random.default_rng(5)
    n = 40 * W
    x = rng.integers(0, cfg["vocab_size"], (n, SEQ)).astype(np.int32)
    y = rng.integers(0, 8, n).astype(np.int32)
    shards = np.array_split(rng.permutation(n), W)
    ring = np.roll(np.eye(W, dtype=int), 1, 1)
    ring = ring + ring.T
    chords = ring.copy()
    chords[0, W // 2] = chords[W // 2, 0] = 1
    plans = [(rng.integers(1, TAU_MAX + 1, W), adj)
             for adj in (ring, chords, ring)]
    return (x, y), shards, x[:64], y[:64], plans


def replay(model, cfg, inputs, chips: int):
    import jax

    from reference import dfl
    data, shards, tx, ty, plans = inputs
    with jax.default_matmul_precision("highest"):
        return dfl.replay(model, cfg, data, shards, tx, ty, seed=11,
                          plans=plans, batch=BATCH, lr=0.1, lr_decay=0.98,
                          tau_max=TAU_MAX, eval_subset=64,
                          measure_rounds=MEASURED, chips=chips)


def rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def check_agree(model, cfg, inputs) -> None:
    one = replay(model, cfg, inputs, 1)
    four = replay(model, cfg, inputs, 4)
    gaps = {"loss": rel(four.loss, one.loss),
            "consensus": rel(four.consensus, one.consensus),
            "update": rel(four.update, one.update),
            "change": rel([four.change[k] for k in one.change],
                          list(one.change.values())),
            "first_grad": rel([four.first_grad[k] for k in one.first_grad],
                              list(one.first_grad.values()))}
    for h in range(MEASURED):
        for k, v in one.measured[h].items():
            if k == "edge":     # the diagonal is 0 on both sides
                v = v + 1.0
                gaps[f"measured {h} {k}"] = rel(four.measured[h][k] + 1.0, v)
            else:
                gaps[f"measured {h} {k}"] = rel(four.measured[h][k], v)
    assert four.change.keys() == one.change.keys()
    assert len(one.change) == W * 12 and len(one.measured) == MEASURED
    bad = {k: v for k, v in gaps.items() if not v <= TOL}
    assert not bad, f"split over 4 chips departs from one device: {bad}"
    print(f"ok: 4 chips agree with one device within {TOL:g}: "
          f"{ {k: f'{v:.2e}' for k, v in gaps.items()} }")


def check_exchanges(model, cfg) -> None:
    import jax
    import jax.numpy as jnp

    from reference import dfl
    f = dfl.programs(model, json.dumps(cfg, sort_keys=True), W, "float32",
                     "", 4)
    params = f.init(jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(params)
    big = max(leaves, key=lambda l: l.size)
    bx = jnp.zeros((W, TAU_MAX, BATCH, SEQ), jnp.int32)
    by = jnp.zeros((W, TAU_MAX, BATCH), jnp.int32)
    taus = jnp.ones((W,), jnp.int32)
    tx, ty = bx[0, 0], by[0, 0]

    def collectives(jitted, *args):
        text = jitted.lower(*args).compile().as_text()
        return {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                for op in ("all-gather", "all-reduce", "collective-permute",
                           "reduce-scatter", "all-to-all")}

    none = {"local_sgd": collectives(f.local_sgd, params, bx, by, taus,
                                     jnp.float32(0.1)),
            "first_grads": collectives(f.first_grads, params, bx, by),
            "rows_norms": collectives(f.rows_norms, params, params)}
    for name, got in none.items():
        assert not any(got.values()), (name, got)
    ev = collectives(f.evaluate, params, tx, ty)
    assert ev["all-reduce"] and not (ev["all-gather"] or
                                     ev["collective-permute"]), ev
    # the mix and the distances: one program a leaf, one gather in each;
    # a chip's temporaries are one leaf of the whole fleet and at most
    # its own share of that leaf besides
    m = jnp.eye(W, dtype=jnp.float32)
    temps = {}
    for name, leaf_fn, args in (("mix", f.mix_leaf, (big, m)),
                                ("pairwise", f.pair_leaf, (big,))):
        compiled = leaf_fn.lower(*args).compile()
        got = collectives(leaf_fn, *args)
        assert got["all-gather"] == 1 and sum(got.values()) == 1, (name, got)
        temp = temps[name] = compiled.memory_analysis().temp_size_in_bytes
        fleet_leaf = big.size * big.dtype.itemsize
        assert temp <= fleet_leaf * (1 + 1 / 4) + (1 << 16), \
            (name, temp, fleet_leaf)
    print(f"ok: no collective in local SGD, first gradients or norms; "
          f"evaluation {ev}; the mix and the distances gather one leaf "
          f"({big.shape}) a program, temporaries {temps} bytes")


def digests(model, cfg) -> dict:
    """sha256 of each one-device program's lowered text."""
    import jax
    import jax.numpy as jnp

    from reference import dfl
    f = dfl.programs(model, json.dumps(cfg, sort_keys=True), W, "float32",
                     "")
    with jax.default_matmul_precision("highest"):
        params = jax.eval_shape(f.init, jax.random.PRNGKey(0))
        one = jax.eval_shape(lambda p: jax.tree.map(lambda l: l[0], p),
                             params)
        ex = jax.ShapeDtypeStruct((W, 256, SEQ), jnp.int32)
        ey = jax.ShapeDtypeStruct((W, 256), jnp.int32)
        px = jax.ShapeDtypeStruct((W, 32, SEQ), jnp.int32)
        py = jax.ShapeDtypeStruct((W, 32), jnp.int32)
        bx = jax.ShapeDtypeStruct((W, TAU_MAX, BATCH, SEQ), jnp.int32)
        by = jax.ShapeDtypeStruct((W, TAU_MAX, BATCH), jnp.int32)
        tx = jax.ShapeDtypeStruct((64, SEQ), jnp.int32)
        ty = jax.ShapeDtypeStruct((64,), jnp.int32)
        m = jax.ShapeDtypeStruct((W, W), jnp.float32)
        args = {"init": (jax.random.PRNGKey(0),),
                "local_sgd": (params, bx, by,
                              jax.ShapeDtypeStruct((W,), jnp.int32),
                              jax.ShapeDtypeStruct((), jnp.float32)),
                "mix": (params, m), "evaluate": (params, tx, ty),
                "measure_one": (one, one, ex, ey, px, py),
                "pairwise": (params,), "first_grads": (params, bx, by),
                "rows_norms": (params, params)}
        return {k: hashlib.sha256(getattr(f, k).lower(*a).as_text()
                                  .encode()).hexdigest()
                for k, a in sorted(args.items())}


def check_one_device(model, cfg) -> None:
    got = digests(model, cfg)
    assert got == ONE_DEVICE, json.dumps(got, indent=1)
    print(f"ok: the one-device programs are unchanged ({len(got)} digests)")


def main() -> int:
    if FLAG not in os.environ.get("XLA_FLAGS", "") or \
            os.environ.get("JAX_PLATFORMS") != "cpu":
        return relaunch()
    import jax

    import reference
    assert len(jax.devices()) >= 4, jax.devices()
    cfg = tiny_config()
    model = reference.load(cfg["reference"])
    check_one_device(model, cfg)
    check_agree(model, cfg, experiment(cfg))
    check_exchanges(model, cfg)
    return 0


if __name__ == "__main__":
    sys.exit(main())

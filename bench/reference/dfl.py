"""Plain reference of one decentralised-learning experiment.

Replays, in straight ``jax.numpy``, what the configuration and the
traffic mix state one synchronous round does (paper Eq. 3, 5, 6 and
Alg. 1 lines 4-5), given the round's plan:

1. every worker ``i`` takes ``tau_i`` plain SGD steps at the round's
   learning rate ``lr * lr_decay ** h`` on batches drawn, with
   replacement, from its own shard;
2. the fleet gossips once with the uniform mixing matrix of the round's
   topology (Eq. 6: ``1 / (max degree + 1)`` on each link, the rest on
   the diagonal); a round without links does not mix;
3. each worker is evaluated on the first ``eval_subset`` test rows, and
   the fleet's loss is their mean; its consensus distance is the mean
   distance of the workers to their average;
4. in the first ``measure_rounds`` rounds of an adaptive strategy, each
   worker measures Alg. 1's quantities: its loss on the whole fleet's
   evaluation stack, the smoothness estimate ``|g(p) - g(q)| / |p - q|``
   against its parameters before the round, the gradient noise of the
   probe batch, and its update norm; plus the pairwise distances.

The run's seed drives one ``numpy`` generator, which first draws each
worker's 256-row evaluation stack (inputs, then labels, in two separate
draws) and then, round by round and worker by worker, ``max(tau)``
batches. The reference draws the same way, so it trains on the same rows.

Nothing of the program is imported. The plan (taus and topology of each
round) is the control plane's decision, which the reference takes as
its input; the data set and the shards are the experiment's inputs.
"""
from __future__ import annotations

import functools
import json
import time
from dataclasses import dataclass, field
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

EVAL_STACK = 256        # rows per worker in Alg. 1's evaluation stack
PROBE_ROWS = 32         # of which the first 32 form the noise probe


@dataclass
class Replay:
    """What the reference computed for one experiment."""
    loss: list[float] = field(default_factory=list)       # per round
    consensus: list[float] = field(default_factory=list)  # per round
    update: list[float] = field(default_factory=list)     # mean |dx_i|
    change: dict = field(default_factory=dict)   # (worker, leaf) -> norm
    first_grad: dict = field(default_factory=dict)  # (worker, leaf) -> norm
    measured: list[dict] = field(default_factory=list)  # Alg. 1, per round
    clock: dict = field(default_factory=dict)   # reference/clock.py fields
    seconds: dict = field(default_factory=dict)   # host clock, per phase


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def uniform_mixing(adj: np.ndarray) -> np.ndarray:
    """Eq. 6 on a 0/1 symmetric adjacency; identity without links."""
    adj = np.asarray(adj, np.float64)
    if adj.sum() == 0:
        return np.eye(adj.shape[0])
    m = adj / (adj.sum(axis=1).max() + 1.0)
    np.fill_diagonal(m, 0.0)
    return m + np.diag(1.0 - m.sum(axis=1))


@functools.lru_cache(maxsize=None)
def programs(model, cfg_json: str, w: int, dtype_name: str, fault: str):
    """The reference's jitted steps for one model, fleet size, precision
    and fault, built once a process: data, plans and learning rates are
    arguments, so the compiled programs serve every seed (and the
    persistent compile cache serves every run)."""
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)
    loss_fn = lambda p, x, y: model.loss(p, x, y, cfg)  # noqa: E731
    grad_fn = jax.grad(loss_fn)

    def sq(tree):
        return sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                   for l in jax.tree.leaves(tree))

    def rows_norms(a, b):
        return [jnp.sqrt(jnp.sum(jnp.square(
            (x.astype(jnp.float32) - y.astype(jnp.float32)).reshape(w, -1)),
            axis=1)) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]

    def init(key):
        return jax.tree.map(lambda l: jnp.broadcast_to(l, (w,) + l.shape),
                            model.init(cfg, key, dtype))

    def local_sgd(params, bx, by, taus, lr_h):
        def one(p, bxw, byw, tau):
            def step(k, p):
                xk, yk = bxw[k], byw[k]
                if fault == "half_batch":
                    xk, yk = xk[:xk.shape[0] // 2], yk[:yk.shape[0] // 2]
                g = grad_fn(p, xk, yk)
                return jax.tree.map(
                    lambda a, b: (a - lr_h.astype(a.dtype) * b).astype(
                        a.dtype), p, g)
            return jax.lax.fori_loop(0, tau, step, p)
        return jax.vmap(one)(params, bx, by, taus)

    def mix(params, m):
        return jax.tree.map(
            lambda l: jnp.tensordot(m.astype(l.dtype), l, axes=1), params)

    def evaluate(params, tx, ty):
        losses = jax.vmap(lambda p: loss_fn(p, tx, ty))(params)
        mean = jax.tree.map(lambda l: jnp.mean(l.astype(jnp.float32), 0),
                            params)
        dev = sum(jnp.sum(jnp.square(l.astype(jnp.float32) - m[None])
                          .reshape(w, -1), axis=1)
                  for l, m in zip(jax.tree.leaves(params),
                                  jax.tree.leaves(mean)))
        return jnp.mean(losses), jnp.mean(jnp.sqrt(dev))

    def measure_one(p, q, ex, ey, px, py):
        g_p = grad_fn(p, ex, ey)
        g_q = grad_fn(q, ex, ey)
        g_s = grad_fn(p, px, py)
        diff = lambda a, b: jax.tree.map(jnp.subtract, a, b)  # noqa: E731
        den = jnp.sqrt(sq(diff(p, q)))
        return {"loss": loss_fn(p, ex, ey),
                "smooth_l": jnp.sqrt(sq(diff(g_p, g_q))) / jnp.maximum(
                    den, 1e-8),
                "sigma": jnp.sqrt(sq(diff(g_s, g_p))), "update": den}

    def pairwise(params):
        return jnp.sqrt(sum(
            jnp.sum(jnp.square(l.astype(jnp.float32)[:, None]
                               - l.astype(jnp.float32)[None]).reshape(
                w, w, -1), axis=2) for l in jax.tree.leaves(params)))

    def first_grads(params, bx, by):
        g = jax.vmap(lambda p, xb, yb: grad_fn(p, xb[0], yb[0]))(
            params, bx, by)
        return rows_norms(g, jax.tree.map(jnp.zeros_like, g))

    def pick(params, i):
        return jax.tree.map(lambda l: l[i], params)

    return SimpleNamespace(**{k: jax.jit(v) for k, v in dict(
        init=init, local_sgd=local_sgd, mix=mix, evaluate=evaluate,
        measure_one=measure_one, pairwise=pairwise, first_grads=first_grads,
        rows_norms=rows_norms).items()}, pick=jax.jit(pick,
                                                      static_argnums=1))


def replay(model, cfg: dict, data, shards, test_x, test_y, *, seed: int,
           plans, batch: int, lr: float, lr_decay: float, tau_max: int,
           eval_subset: int, measure_rounds: int, dtype=jnp.float32,
           fault: str = "") -> Replay:
    """Follow the experiment's rounds under ``plans`` ([(taus, adj)] per
    round). ``model`` has ``init(cfg, key, dtype)`` and
    ``loss(params, x, y, cfg)``. ``fault`` plants a fault in the
    reference for the check's readings ("half_batch": the SGD step sees
    the first half of each batch)."""
    w = len(shards)
    x_all, y_all = data
    f = programs(model, json.dumps(cfg, sort_keys=True), w,
                 jnp.dtype(dtype).name, fault)

    rng = np.random.default_rng(seed)
    ex = np.stack([x_all[sh[rng.integers(0, len(sh), EVAL_STACK)]]
                   for sh in shards])
    ey = np.stack([y_all[sh[rng.integers(0, len(sh), EVAL_STACK)]]
                   for sh in shards])
    ex, ey = jnp.asarray(ex), jnp.asarray(ey)
    px, py = ex[:, :PROBE_ROWS], ey[:, :PROBE_ROWS]
    tx = jnp.asarray(test_x[:eval_subset])
    ty = jnp.asarray(test_y[:eval_subset])

    init = f.init(jax.random.PRNGKey(seed))
    params = init
    names = leaf_names(init)
    out = Replay()
    clock = {"t": time.perf_counter()}

    def lap(phase):
        t = time.perf_counter()
        out.seconds[phase] = out.seconds.get(phase, 0.0) + t - clock["t"]
        clock["t"] = t

    lap("inputs")
    for h, (taus, adj) in enumerate(plans):
        taus = np.clip(np.asarray(taus), 1, tau_max).astype(np.int32)
        cap = int(max(taus.max(), 1))
        bx = np.zeros((w, tau_max, batch) + x_all.shape[1:], x_all.dtype)
        by = np.zeros((w, tau_max, batch), np.int32)
        for i, sh in enumerate(shards):
            sel = sh[rng.integers(0, len(sh), (cap, batch))]
            bx[i, :cap], by[i, :cap] = x_all[sel], y_all[sel]
        bx, by = jnp.asarray(bx), jnp.asarray(by)
        if h == 0:
            for i, n in enumerate(f.first_grads(params, bx, by)):
                for wi, v in enumerate(np.asarray(n)):
                    out.first_grad[(wi, names[i])] = float(v)
        lr_h = jnp.float32(lr * lr_decay ** h)
        prev = params
        params = f.local_sgd(params, bx, by, jnp.asarray(taus), lr_h)
        params = f.mix(params, jnp.asarray(uniform_mixing(adj), jnp.float32))
        jax.block_until_ready(params)
        lap("sgd_and_mix")
        fleet_loss, consensus = f.evaluate(params, tx, ty)
        out.loss.append(float(fleet_loss))
        out.consensus.append(float(consensus))
        upd = sum(np.square(np.asarray(n))
                  for n in f.rows_norms(params, prev))
        out.update.append(float(np.mean(np.sqrt(upd))))
        lap("evaluation")
        if h < measure_rounds:
            rows = [f.measure_one(f.pick(params, i), f.pick(prev, i),
                                  ex, ey, px, py) for i in range(w)]
            rows = {k: np.array([float(r[k]) for r in rows]) for k in rows[0]}
            out.measured.append({
                "loss": float(np.mean(rows["loss"])),
                "smooth_l": float(np.median(rows["smooth_l"])),
                "sigma": float(np.median(rows["sigma"])),
                "update": rows["update"],
                "edge": np.asarray(f.pairwise(params), np.float64)})
            lap("measurement")
        del prev
    for i, n in enumerate(f.rows_norms(params, init)):
        for wi, v in enumerate(np.asarray(n)):
            out.change[(wi, names[i])] = float(v)
    return out

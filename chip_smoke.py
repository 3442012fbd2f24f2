"""On-chip smoke test of the fused DFL engine.

Drives the system's main path, ``run_algorithm(..., fused=True)`` into
``core/fused.run_dfl_fused``, on a TPU, in one process:

- ``fedhp-published``: FedHP on the registry dense family at
  SmolLM-360M's published widths (d_model 960, 15 heads, 5 KV heads,
  d_ff 2560), cut only in depth, vocabulary, sequence length and fleet
  size; the loss must be finite and fall, and FedHP must plan tau and
  topology every round.
- one phase per fused-engine kernel on the paper's 30-worker testbed
  (``benchmarks/run.py --full``): dense gossip, edge-list gossip, int8,
  top-k and trimmed-mean gossip under attackers. Each runs D-PSGD through
  the fused engine and through the reference engine on the chip; host
  record fields must be bit-identical and device metrics agree within
  ``DEVICE_TOL``.

Every phase checks that its segment lowered Pallas kernels for the chip
(``tpu_custom_call``), not interpret mode.

    python chip_smoke.py              # one chip, all phases above
    python chip_smoke.py --chips 4    # only the sharded path on 4 chips
                                      # against the same run on 1 chip

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU
the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# host-replayed record fields: both engines run the same control plane
EXACT = ("round", "round_time", "waiting_time", "mean_tau", "num_links",
         "cumulative_time")
# Device metrics of the fused engine vs the reference engine, both run
# under jax.default_matmul_precision("highest"). At the TPU's default
# precision an f32 matmul takes one bf16 pass: the reference engine mixes
# with a [W, W] matmul while the fused dense path mixes with an exact f32
# kernel, so the two would differ by bf16 rounding (~4e-3 relative) that
# says nothing about the kernels. Under "highest" both are f32 to within
# re-association, as on the CPU (tests/test_fused_equivalence.py), except:
# accuracy is a count over 512 test samples and may flip one sample whose
# logits tie to the last bit (1/512 = 1.95e-3); and a codec's rounding
# turns a last-bit difference into a whole quantisation step on a rare
# coordinate, which moves the consensus distance up to ~2e-3 (the CPU
# harness's COMPRESSED_TOL).
DEVICE_TOL = {"accuracy": 2e-3, "loss": 1e-4, "consensus": 1e-4}
CODEC_TOL = {"accuracy": 2e-3, "loss": 1e-4, "consensus": 2e-3}

# the published-width worker: SmolLM-360M widths, cut in depth (32 -> 4),
# vocabulary (49152 -> 6144), sequence and fleet to fit one v5e chip
LAYERS, VOCAB, SEQ, FLEET = 4, 6144, 6, 4
FEDHP_ROUNDS = 4
KERNEL_ROUNDS = 4
SHARDED_WORKERS, SHARDED_ROUNDS, SHARDED_SEQ = 8, 2, 16


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _abstract(a):
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
    return a


class SegmentSpy:
    """Stands in for ``fused._scan_segment`` and keeps its last call, so
    the smoke can lower that segment again and read which kernels it
    holds; also times each call (the first one compiles)."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = 0
        self.seconds: list[float] = []
        self.last = None

    def __call__(self, *args, **kwargs):
        self.calls += 1
        # shapes only: the spy must not keep a round's [W, P] buffers alive
        self.last = (jax.tree.map(_abstract, args), kwargs)
        t = time.perf_counter()
        out = self.fn(*args, **kwargs)
        jax.block_until_ready(out)
        self.seconds.append(time.perf_counter() - t)
        return out

    def reset(self):
        self.calls, self.seconds, self.last = 0, [], None

    def lowered_text(self) -> str:
        args, kwargs = self.last
        return self.fn.lower(*args, **kwargs).as_text()


class CacheCounter:
    """Persistent compilation cache hits and misses, from JAX's events."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def require_tpu() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev}); there is no CPU "
              "fallback", file=sys.stderr)
        sys.exit(2)
    return dev


def check_compiled_kernels(spy: SegmentSpy, phase: str) -> None:
    text = spy.lowered_text()
    n = text.count("tpu_custom_call")
    log(f"[{phase}] lowered segment holds {n} tpu_custom_call kernel "
        "call(s): compiled Pallas, not interpret mode")
    check(n > 0, f"{phase}: the segment holds no compiled Pallas kernel")
    spy.last = None


def compare(phase: str, h_ref, h_fus, tol: dict,
            names=("reference", "fused")) -> None:
    check(len(h_ref.records) == len(h_fus.records),
          f"{phase}: {len(h_ref.records)} {names[0]} rounds vs "
          f"{len(h_fus.records)} {names[1]}")
    a, b = h_ref.as_arrays(), h_fus.as_arrays()
    for k in EXACT:
        check(np.array_equal(a[k], b[k]),
              f"{phase}: host field {k} differs: {a[k]} vs {b[k]}")
    for k, t in tol.items():
        diff = float(np.max(np.abs(a[k] - b[k])))
        log(f"[{phase}] {k}: {names[0]} {np.round(a[k], 6).tolist()} "
            f"{names[1]} {np.round(b[k], 6).tolist()} max|diff| {diff:.3g} "
            f"(rtol = atol = {t})")
        check(np.all(np.isfinite(b[k])), f"{phase}: {k} not finite")
        check(np.allclose(b[k], a[k], rtol=t, atol=t),
              f"{phase}: {k} differs by {diff:.3g} > tolerance {t}")
    log(f"[{phase}] host fields {', '.join(EXACT)}: bit-identical")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_fedhp_published(spy: SegmentSpy) -> None:
    from repro.configs.base import FedHPConfig
    from repro.configs.smollm_360m import CONFIG, dfl_model_spec
    from repro.core.experiment import run_algorithm
    from repro.core.modelspec import get_adapter

    phase = "fedhp-published"
    spec = dfl_model_spec(layers=LAYERS, vocab=VOCAB, seq=SEQ)
    p = get_adapter(spec).param_count
    log(f"[{phase}] worker model {spec}: {p:,} parameters per worker")
    log(f"[{phase}] widths as published by {CONFIG.name}: d_model "
        f"{CONFIG.d_model}, heads {CONFIG.num_heads}, kv heads "
        f"{CONFIG.num_kv_heads}, d_ff {CONFIG.d_ff} (none cut)")
    for what, full, cut, why in (
            ("layers", CONFIG.num_layers, LAYERS,
             "depth multiplies parameters, and every copy of the [W, P] "
             "matrix the round holds; 4 layers is the least kept"),
            ("vocab", CONFIG.vocab_size, VOCAB,
             "1/8 of the published vocabulary, the least kept; embedding "
             "and head are 2 x vocab x d_model of P"),
            ("seq", 2048, SEQ,
             "each worker's Alg. 1 measurement takes three gradients on "
             "the whole fleet's [W, 256, seq] eval stack (core/engine.py "
             "_measure_worker), so measurement memory grows as "
             "W^2 x 256 x seq; the segment fits 16 GB only this short"),
            ("workers", 30, FLEET,
             "the same W^2 measurement term: at seq 6, W=4 peaks at 13.7 "
             "of the 15.75 GB usable (compiled memory_analysis)")):
        log(f"[{phase}] cut {what}: {full} -> {cut} ({why})")
    cfg = FedHPConfig(num_workers=FLEET, rounds=FEDHP_ROUNDS, tau_init=4,
                      tau_max=4, lr=0.1, batch_size=8, seed=0, model=spec)
    log(f"[{phase}] tau_max {cfg.tau_max}: a round's largest tau buckets "
        "to a scan length of 1, 2 or 4, and each bucket compiles the round "
        "program once")
    spy.reset()
    t = time.perf_counter()
    h = run_algorithm("fedhp", cfg, rounds=FEDHP_ROUNDS, fused=True,
                      num_samples=1200)
    log(f"[{phase}] {FEDHP_ROUNDS} rounds in {time.perf_counter() - t:.1f} "
        f"s; segment seconds (first compiles) "
        f"{[round(s, 2) for s in spy.seconds]}")
    check_compiled_kernels(spy, phase)
    arr = h.as_arrays()
    for r in h.records:
        log(f"[{phase}] round {r.round}: loss {r.loss:.5f} accuracy "
            f"{r.accuracy:.6f} mean_tau {r.mean_tau:.2f} links "
            f"{r.num_links} consensus {r.consensus:.5g}")
    check(len(h.records) == FEDHP_ROUNDS, f"{phase}: rounds cut short")
    check(spy.calls == FEDHP_ROUNDS,
          f"{phase}: {spy.calls} plan segments for {FEDHP_ROUNDS} rounds; "
          "FedHP must replan every round")
    check(np.all(np.isfinite(arr["loss"])), f"{phase}: loss not finite")
    check(arr["loss"][-1] < arr["loss"][0],
          f"{phase}: loss did not fall: {arr['loss'].tolist()}")
    check(np.all(arr["mean_tau"] >= 1) and np.all(arr["num_links"] >= 1),
          f"{phase}: a round without a tau or topology plan")
    log(f"[{phase}] ok: loss {arr['loss'][0]:.5f} -> {arr['loss'][-1]:.5f}, "
        "finite, one tau + topology plan per round")


def kernel_phases(spy: SegmentSpy) -> None:
    from benchmarks.run import SPREAD, base_cfg
    from repro.core.experiment import run_algorithm

    cfg = replace(base_cfg(full=True), rounds=KERNEL_ROUNDS)
    phases = {
        "dense": ({}, DEVICE_TOL),
        "sparse": ({"gossip": "sparse"}, DEVICE_TOL),
        "int8": ({"compress": "int8"}, CODEC_TOL),
        "topk": ({"compress": "topk:0.1"}, CODEC_TOL),
        "trimmed": ({"robust": "trimmed:1",
                     "byzantine": (3, 11, 19), "byzantine_attack":
                     "signflip"}, DEVICE_TOL),
    }
    log(f"[kernels] fleet: benchmarks/run.py --full, W={cfg.num_workers} "
        f"MLP workers, D-PSGD, {KERNEL_ROUNDS} rounds, non-IID p=0.4")
    for phase, (over, tol) in phases.items():
        c = replace(cfg, **over)
        spy.reset()
        t = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            h_ref = run_algorithm("dpsgd", c, non_iid_p=0.4,
                                  rounds=KERNEL_ROUNDS, spread=SPREAD)
            t_ref = time.perf_counter() - t
            h_fus = run_algorithm("dpsgd", c, non_iid_p=0.4,
                                  rounds=KERNEL_ROUNDS, spread=SPREAD,
                                  fused=True)
            t_fus = time.perf_counter() - t - t_ref
            check_compiled_kernels(spy, phase)
        log(f"[{phase}] {over or 'dense gossip'}: reference engine "
            f"{t_ref:.1f} s, fused engine {t_fus:.1f} s (compiles "
            "included)")
        compare(phase, h_ref, h_fus, tol)
        log(f"[{phase}] ok")


def phase_sharded(spy: SegmentSpy) -> None:
    from repro.configs.base import FedHPConfig
    from repro.configs.smollm_360m import dfl_model_spec
    from repro.core.experiment import run_algorithm
    from repro.launch.mesh import make_worker_mesh

    phase = "sharded"
    check(len(jax.devices()) >= 4, f"{phase}: needs 4 chips, found "
          f"{len(jax.devices())}")
    spec = dfl_model_spec(layers=LAYERS, vocab=VOCAB, seq=SHARDED_SEQ)
    cfg = FedHPConfig(num_workers=SHARDED_WORKERS, rounds=SHARDED_ROUNDS,
                      tau_init=2, tau_max=2, lr=0.1, batch_size=8, seed=0,
                      model=spec, gossip="sparse")
    log(f"[{phase}] D-PSGD, W={SHARDED_WORKERS} workers of {spec}, "
        f"edge-list gossip, {SHARDED_ROUNDS} rounds: 4-chip worker mesh "
        "against the same run on one chip")
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        h_one = run_algorithm("dpsgd", cfg, rounds=SHARDED_ROUNDS,
                              fused=True, num_samples=1200)
        check_compiled_kernels(spy, "one-chip")
        h_one.final_params = None
        t_one = time.perf_counter() - t
        h_four = run_algorithm("dpsgd", cfg, rounds=SHARDED_ROUNDS,
                               fused=True, num_samples=1200,
                               mesh=make_worker_mesh(4))
    log(f"[{phase}] one chip {t_one:.1f} s, four chips "
        f"{time.perf_counter() - t - t_one:.1f} s (compiles included)")
    compare(phase, h_one, h_four, DEVICE_TOL, ("one-chip", "four-chip"))
    leaves = jax.tree.leaves(h_four.final_params)
    whole = sum(l.nbytes for l in leaves)
    per_dev = {}
    for l in leaves:
        for s in l.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + \
                s.data.nbytes
    log(f"[{phase}] [W, P] parameters {whole:,} bytes; held per device "
        f"{per_dev}")
    for d in jax.devices()[:4]:
        used = (d.memory_stats() or {}).get("bytes_in_use")
        log(f"[{phase}] device {d.id} memory_stats bytes_in_use {used} "
            f"({used / whole:.3f} of [W, P])" if used else
            f"[{phase}] device {d.id} reports no memory_stats")
        check(math.isclose(per_dev.get(d.id, 0), whole / 4, rel_tol=1e-6),
              f"{phase}: device {d.id} holds {per_dev.get(d.id, 0)} bytes, "
              f"not a quarter of {whole}")
        check(used is None or used < whole / 2,
              f"{phase}: device {d.id} has {used} bytes in use, more than "
              "half the fleet: the shards are not spread")
    log(f"[{phase}] ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on a 4-chip mesh "
                         "and the one-chip run it is compared with")
    args = ap.parse_args(argv)
    dev = require_tpu()

    from repro.core import fused
    from repro.launch.cache import enable_compile_cache
    check("xla_force_host_platform_device_count" not in
          os.environ.get("XLA_FLAGS", ""),
          "an import forced virtual host devices")
    log(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")
    log(f"compile cache: {enable_compile_cache()}")
    cache = CacheCounter()
    spy = SegmentSpy(fused._scan_segment)
    fused._scan_segment = spy
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(spy)
    else:
        phase_fedhp_published(spy)
        kernel_phases(spy)
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
        f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

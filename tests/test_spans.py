"""The fused engine's profiler spans.

- Device scopes: every phase of the round program (``_scan_segment`` and
  its sharded twin) runs under a ``jax.named_scope`` named ``dfl.<phase>``,
  so each operation of the compiled program carries its phase in the
  ``op_name`` metadata that the profiler's device trace reports.
- Host spans: ``run_dfl_fused`` wraps its control plane (init, segment,
  precompute, plan, upload, dispatch, sync, observe) in
  ``jax.profiler`` annotations on the same clock.
- Neither changes what a run computes.
"""
from __future__ import annotations

import glob
import os
import re
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import FedHPConfig
from repro.core import fused
from repro.core.experiment import run_algorithm

CFG = FedHPConfig(num_workers=4, rounds=2, tau_init=2, tau_max=4, lr=0.1,
                  batch_size=8, seed=5)

PHASES = ("dfl.join", "dfl.local_sgd", "dfl.codec", "dfl.robust",
          "dfl.mix", "dfl.evaluation", "dfl.alg1_measure")


class _Captured(Exception):
    pass


def _capture(monkeypatch, name, algo, cfg):
    """The arguments ``run_dfl_fused`` passes to its first segment call of
    the round program ``name``; the call itself is not made."""
    seen = {}

    def grab(*args, **kw):
        seen.update(args=args, kw=kw)
        raise _Captured

    monkeypatch.setattr(fused, name, grab)
    with pytest.raises(_Captured):
        run_algorithm(algo, cfg, rounds=2, fused=True, num_samples=400)
    return seen["args"], seen["kw"]


@pytest.mark.parametrize("algo, cfg, want", [
    ("dpsgd", CFG, {"dfl.join", "dfl.local_sgd", "dfl.mix",
                    "dfl.evaluation"}),
    ("dpsgd", replace(CFG, gossip="sparse"),
     {"dfl.join", "dfl.local_sgd", "dfl.mix", "dfl.evaluation"}),
    ("fedhp", CFG, {"dfl.join", "dfl.local_sgd", "dfl.mix",
                    "dfl.evaluation", "dfl.alg1_measure"}),
    ("dpsgd", replace(CFG, compress="int8"),
     {"dfl.join", "dfl.local_sgd", "dfl.codec", "dfl.mix",
      "dfl.evaluation"}),
    ("dpsgd", replace(CFG, compress="topk:0.1"),
     {"dfl.join", "dfl.local_sgd", "dfl.codec", "dfl.mix",
      "dfl.evaluation"}),
    ("dpsgd", replace(CFG, robust="trimmed:1"),
     {"dfl.join", "dfl.local_sgd", "dfl.robust", "dfl.evaluation"}),
    ("fedhp", replace(CFG, sharded=True, gossip="sparse"),
     {"dfl.join", "dfl.local_sgd", "dfl.mix", "dfl.evaluation",
      "dfl.alg1_measure"}),
], ids=["dense", "sparse", "measure", "int8", "topk", "trimmed",
        "sharded"])
def test_compiled_round_program_carries_phase_scopes(monkeypatch, algo, cfg,
                                                     want):
    """The compiled round program names every phase its static branch
    reaches, and none it does not."""
    name = "_scan_segment_sharded" if cfg.sharded else "_scan_segment"
    program = getattr(fused, name)
    args, kw = _capture(monkeypatch, name, algo, cfg)
    hlo = program.lower(*args, **kw).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    found = {p for p in PHASES
             if any(f"/{p}/" in n or n.endswith(f"/{p}") for n in names)}
    assert found == want
    # the codec's mixing delta nests inside the codec's scope, and no
    # phase nests inside itself
    if "dfl.codec" in want:
        assert any("/dfl.codec/dfl.mix/" in n for n in names)
    assert not any(f"{p}/{p}/" in n for n in names for p in PHASES)


def _host_spans(path):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dfl."):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced_fedhp(tmp_path_factory):
    """A 3-round FedHP run under the profiler, and its host spans."""
    cfg = replace(CFG, rounds=3)
    path = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        hist = run_algorithm("fedhp", cfg, rounds=3, fused=True,
                             num_samples=400)
        jax.block_until_ready(hist.final_params)
    finally:
        jax.profiler.stop_trace()
    return cfg, hist, _host_spans(path), path


def test_host_spans_nest_and_count_per_round(traced_fedhp):
    _, _, spans, _ = traced_fedhp

    def named(n):
        return [s for s in spans if s[0] == n]

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    segments = named("dfl.segment")
    assert [s[3]["step_num"] for s in segments] == [0, 1, 2]
    (init,) = named("dfl.init")
    assert init[2] <= segments[0][1]
    for n in ("dfl.precompute", "dfl.plan", "dfl.upload", "dfl.dispatch",
              "dfl.sync", "dfl.observe"):
        got = named(n)
        # one a round: FedHP replans every round, one round a segment
        assert [s[3]["h"] for s in got] == [0, 1, 2], n
        for s, seg in zip(got, segments):
            assert inside(s, seg), (n, s, seg)
    for plan, pre in zip(named("dfl.plan"), named("dfl.precompute")):
        assert inside(plan, pre)
    order = ["dfl.precompute", "dfl.upload", "dfl.dispatch", "dfl.sync",
             "dfl.observe"]
    for h in range(3):
        starts = [named(n)[h][1] for n in order]
        assert starts == sorted(starts)


def test_traced_history_equals_untraced(traced_fedhp):
    cfg, traced, _, _ = traced_fedhp
    plain = run_algorithm("fedhp", cfg, rounds=3, fused=True,
                          num_samples=400)
    assert [r.__dict__ for r in traced.records] == \
        [r.__dict__ for r in plain.records]
    for a, b in zip(jax.tree.leaves(traced.final_params),
                    jax.tree.leaves(plain.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _bench_module(name):
    root = Path(__file__).resolve().parents[1]
    if str(root / "bench") not in sys.path:
        sys.path.append(str(root / "bench"))
    return __import__(name)


def test_phase_split_reads_the_round_programs_scopes(traced_fedhp):
    """``bench/phases.py`` finds every traced operation of the FedHP run in
    the profile's own HLO protos and puts device time under each phase
    the round program reaches."""
    phases = _bench_module("phases")
    traces = _bench_module("traces")
    _, _, _, path = traced_fedhp
    src = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                    recursive=True)[0]
    names = phases.hlo_op_names(Path(src).read_bytes())
    ops = phases.device_ops(traces.load_profile(path), 0, 1 << 62, 1)
    assert ops and all(pid in names for _, pid in ops)
    busy = phases.split(ops, names)["busy_s"]
    assert phases.UNMAPPED not in busy
    assert {"dfl.join", "dfl.local_sgd", "dfl.mix", "dfl.evaluation",
            "dfl.alg1_measure"} <= busy.keys(), busy
    assert not {"dfl.codec", "dfl.robust"} & busy.keys(), busy


def test_span_readers_agree_with_hand_counts():
    """``bench/checks/check_spans.py``: the host-span readers against a
    kept CPU trace counted by hand and against one without the spans; the
    per-phase split on known scopes, on a recorded profile's HLO protos,
    and its FLOP terms against ``counts.round_flops``."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(root / "bench" / "checks" / "check_spans.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("ok:") == 5, out.stdout

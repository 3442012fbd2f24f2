"""Self-check of the readers of the program's own spans: the host-span
metrics (``bench/spans.py``, ``planner_ms``, ``host_control_idle_share``)
and the per-phase split of ``bench/phases.py``.

A small CPU trace of ``run_dfl_fused``, kept beside this file, holds the
program's ``dfl.*`` annotations. The CPU has no device plane, so, as in
``check_trace.py``, the XLA CPU runtime's threads stand in for the chip.
Both readers are worked out a second way, by hand on a 1-microsecond
grid, and the two must agree; with the spans taken out, both read None.
The split is checked on operations whose scopes are known, on the HLO
protos of a small profile recorded here, and its FLOP terms against
``counts.round_flops``.

    python3 bench/checks/check_spans.py            # check the kept trace
    python3 bench/checks/check_spans.py --record   # record it anew (CPU)
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent)]

import numpy as np  # noqa: E402

import counts  # noqa: E402
import phases  # noqa: E402
import spans  # noqa: E402
import run  # noqa: E402
import traces  # noqa: E402

KEPT = HERE / "data" / "cpu_spans.xplane.pb"
ROUNDS = 3
CONTROL = run.load_metric("host_control_idle_share").CONTROL


def record() -> None:
    import jax

    from repro.configs.base import FedHPConfig
    from repro.core.experiment import run_algorithm

    cfg = FedHPConfig(num_workers=2, rounds=ROUNDS, tau_init=1, tau_max=2,
                      lr=0.1, batch_size=8, seed=7)
    go = dict(rounds=ROUNDS, fused=True, num_samples=400)
    run_algorithm("fedhp", cfg, **go)          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False      # keeps the kept file small
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            hist = run_algorithm("fedhp", cfg, **go)
            jax.block_until_ready(hist.final_params)
        jax.profiler.stop_trace()
        src = next(Path(d).rglob("*.xplane.pb"))
        KEPT.parent.mkdir(exist_ok=True)
        shutil.copy(src, KEPT)
    print(f"recorded {KEPT} ({KEPT.stat().st_size} bytes)")


def load_cpu_run() -> "traces.TraceRun":
    """The kept CPU trace as the readers see a chip's: the XLA CPU
    threads as the device, the other host lines as the host."""
    pd = traces.load_profile(str(KEPT.parent))
    host = [e for v in traces.line_events(
        pd, lambda n: n.startswith(traces.HOST_PLANE),
        lambda n: not n.startswith("tf_")).values() for e in v]
    win = [e for e in host if e.name == traces.WINDOW]
    assert len(win) == 1, f"expected one window annotation, found {win}"
    lo, hi = win[0].start, win[0].end
    dev = [e for v in traces.line_events(
        pd, lambda n: n.startswith(traces.HOST_PLANE),
        lambda n: n.startswith("tf_XLA")).values() for e in v]
    dev = traces.clip(dev, lo, hi)
    assert dev, "the kept trace holds no XLA CPU operation in the window"
    return traces.TraceRun(
        cell=None, device={}, records=[[{}] * ROUNDS], plans=[],
        eval_rows=0, window_s=(hi - lo) / 1e9,
        busy_s=traces.union_ns(dev) / 1e9, compiles=0, ops={"cpu": dev},
        host=traces.clip(host, lo, hi), lo=lo, hi=hi)


def grid(events, lo: int, hi: int) -> np.ndarray:
    g = np.zeros((hi - lo) // 1000 + 1, bool)
    for e in events:
        g[(e.start - lo) // 1000:(e.end - lo + 999) // 1000] = True
    return g


def check_host() -> None:
    cpu = load_cpu_run()
    lo, hi = cpu.lo, cpu.hi
    names = {e.name for e in cpu.host if e.name.startswith("dfl.")}
    want = set(CONTROL) | {"dfl.segment", "dfl.plan", "dfl.sync"}
    assert names == want, f"spans in the kept trace: {sorted(names)}"
    for n in ("dfl.plan", "dfl.sync", "dfl.segment"):
        got = len(spans.named(cpu, n))
        assert got == ROUNDS, (n, got)

    planner = run.load_metric("planner_ms").read(cpu)
    pl = spans.named(cpu, "dfl.plan", "dfl.observe")
    hand = grid(pl, lo, hi).sum() * 1000 / 1e6 / ROUNDS
    tol = 2e-3 * len(pl) / ROUNDS
    assert abs(planner - hand) <= tol, (planner, hand, tol)

    share = run.load_metric("host_control_idle_share").read(cpu)
    ctl = spans.named(cpu, *CONTROL)
    dev = cpu.ops["cpu"]
    both = ~grid(dev, lo, hi) & grid(ctl, lo, hi)
    hand_share = 100.0 * both.sum() * 1000 / (hi - lo)
    tol = 100.0 * 2000 * (len(dev) + len(ctl)) / (hi - lo)
    assert abs(share - hand_share) <= tol, (share, hand_share, tol)
    assert 0.0 < share < 100.0, share
    print(f"ok: host spans {sorted(names)}; planner_ms {planner:.4f} "
          f"(grid {hand:.4f}); host_control_idle_share {share:.3f} % "
          f"(grid {hand_share:.3f} %)")


def check_without_spans() -> None:
    """A program without the spans (the parent of the commit that added
    them): the readers read nothing and raise nothing."""
    cpu = load_cpu_run()
    cpu.host = [e for e in cpu.host if not e.name.startswith("dfl.")]
    for name in ("planner_ms", "host_control_idle_share"):
        assert run.load_metric(name).read(cpu) is None, name
    print("ok: without the spans both readers read None")


def check_split() -> None:
    """The split on operations whose scopes are known: loops left out,
    the innermost scope wins, an operation found in no program's HLO
    counted apart."""
    ops, t = [], 0
    for name, ms, pid in [("fusion.1", 3, 7), ("while.2", 10, 7),
                          ("copy.3", 2, 7), ("dfl.mix.4", 5, 7),
                          ("fusion.5", 1, 7), ("fusion.1", 4, 8),
                          ("fusion.9", 6, None), ("fusion.6", 1, 7)]:
        ops.append((traces.Event(f"%{name} = f32[8] op()", t,
                                 t + ms * 1_000_000), pid))
        t += ms * 1_000_000
    names = {7: {"fusion.1": "jit(_scan_segment)/while/body/dfl.local_sgd/dot",
                 "while.2": "jit(_scan_segment)/while",
                 "copy.3": "",
                 "dfl.mix.4": "jit(_scan_segment)/while/body/dfl.codec/"
                              "dfl.mix/pallas_call",
                 "fusion.5": "jit(_scan_segment)/while/body/closed_call"},
             8: {"fusion.1": "jit(_scan_segment)/dfl.evaluation/dot",
                 "fusion.9": "jit(_scan_segment)/dfl.alg1_measure/dot"}}
    got = phases.split(ops, names)
    want = {"dfl.local_sgd": 3e-3, phases.NO_OP_NAME: 2e-3,
            "dfl.mix": 5e-3, phases.NO_SCOPE: 1e-3,
            "dfl.evaluation": 4e-3, phases.UNMAPPED: 7e-3}
    assert got["busy_s"].keys() == want.keys(), got["busy_s"]
    for k, v in want.items():
        assert abs(got["busy_s"][k] - v) < 1e-12, (k, got["busy_s"][k], v)
    assert abs(got["scoped_share"] - 100 * 12 / 22) < 1e-9, got
    share = phases.peak_share({"dfl.local_sgd": 6e9, "dfl.mix": 1.0},
                              got["busy_s"], 1e12)
    assert share == {"dfl.local_sgd": 100 * 6e9 / (3e-3 * 1e12)}, share
    print(f"ok: split of known scopes {got['busy_s']}")


def check_flop_terms() -> None:
    """The phase terms of ``phases.phase_flops`` sum to
    ``counts.round_flops`` for every cell, with and without Alg. 1."""
    spec = run.json.loads((run.ROOT / "BENCHMARK.json").read_text())
    n = 0
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        p = cell.traffic
        for taus_sum in (1.0, 9.5, p["workers"] * p["tau_max"]):
            for links in (0, 3, p["workers"] * (p["workers"] - 1)):
                for measured in (False, True):
                    args = (cell.config, p, taus_sum, links, 256, measured)
                    terms = phases.phase_flops(*args)
                    whole = counts.round_flops(*args)
                    assert abs(sum(terms.values()) - whole) <= 1e-12 * whole, \
                        (w["name"], args[2:], terms, whole)
                    assert ("dfl.alg1_measure" in terms) == measured
                    n += 1
    print(f"ok: phase FLOP terms sum to counts.round_flops ({n} cases)")


def check_hlo_scopes() -> None:
    """The split reads a real profile's HLO protos: a small program with
    two scopes, recorded on the CPU, puts its operations under them."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("dfl.local_sgd"):
            y = jnp.sin(x) @ x.T
        with jax.named_scope("dfl.evaluation"):
            return jnp.tanh(y).sum()

    g = jax.jit(f)
    x = jnp.ones((256, 256))
    g(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            for _ in range(3):
                g(x).block_until_ready()
        jax.profiler.stop_trace()
        src = next(Path(d).rglob("*.xplane.pb"))
        names = phases.hlo_op_names(src.read_bytes())
        pd = traces.load_profile(d)
    win = [e for v in traces.line_events(
        pd, lambda n: n.startswith(traces.HOST_PLANE),
        lambda n: True).values() for e in v if e.name == traces.WINDOW][0]
    ops = phases.device_ops(pd, win.start, win.end, 1)
    assert ops and all(pid in names for _, pid in ops), \
        (len(ops), sorted(names))
    busy = phases.split(ops, names)["busy_s"]
    assert {"dfl.local_sgd", "dfl.evaluation"} <= busy.keys(), busy
    assert phases.UNMAPPED not in busy, busy
    print(f"ok: a recorded profile's HLO protos give {sorted(busy)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--record", action="store_true")
    args = ap.parse_args(argv)
    if args.record:
        record()
    check_host()
    check_without_spans()
    check_split()
    check_flop_terms()
    check_hlo_scopes()
    return 0


if __name__ == "__main__":
    sys.exit(main())

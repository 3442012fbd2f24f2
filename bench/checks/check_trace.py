"""Self-check of the trace reduction (``bench/traces.py``) on a small trace
recorded on the CPU and kept beside this file.

The CPU has no device plane, so the check treats the XLA CPU runtime's
threads (lines ``tf_XLA...`` of ``/host:CPU``) as the device and the
harness-style annotations on the Python thread as the host. It then
works out busy time, idle gaps and per-operation sums a second way, by
hand on a 1-microsecond grid, and asserts that both agree.

    python3 bench/checks/check_trace.py            # check the kept trace
    python3 bench/checks/check_trace.py --record   # record it anew (CPU)
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

import traces  # noqa: E402

KEPT = HERE / "data" / "cpu_trace.xplane.pb"


def record() -> None:
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.experiment_init"):
                    y = np.ones((64, 64)) @ np.ones((64, 64))
                with jax.profiler.TraceAnnotation("bench.run_dfl_fused"):
                    f(x + float(y[0, 0])).block_until_ready()
        jax.profiler.stop_trace()
        src = next(Path(d).rglob("*.xplane.pb"))
        KEPT.parent.mkdir(exist_ok=True)
        shutil.copy(src, KEPT)
    print(f"recorded {KEPT} ({KEPT.stat().st_size} bytes)")


def check() -> None:
    pd = traces.load_profile(str(KEPT.parent))
    host_lines = traces.line_events(
        pd, lambda n: n.startswith(traces.HOST_PLANE),
        lambda n: not n.startswith("tf_"))
    host = [e for v in host_lines.values() for e in v]
    win = [e for e in host if e.name == traces.WINDOW]
    assert len(win) == 1, f"expected one window annotation, found {win}"
    lo, hi = win[0].start, win[0].end
    dev = [e for v in traces.line_events(
        pd, lambda n: n.startswith(traces.HOST_PLANE),
        lambda n: n.startswith("tf_XLA")).values() for e in v]
    dev = traces.clip(dev, lo, hi)
    assert dev, "the kept trace holds no XLA CPU operation in the window"

    # by hand: a 1 us grid over the window
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for e in dev:
        grid[(e.start - lo) // 1000:(e.end - lo + 999) // 1000] = True
    busy_hand = grid.sum() * 1000
    busy = traces.union_ns(dev)
    assert abs(busy - busy_hand) <= 2000 * len(dev), (busy, busy_hand)
    idle = sum(e - s for s, e in traces.gaps(dev, lo, hi))
    assert busy + idle == hi - lo, (busy, idle, hi - lo)
    sums = traces.by_name(dev)
    for name, total in sums.items():
        hand = sum(e.end - e.start for e in dev if e.name == name)
        assert total == hand, (name, total, hand)
    assert sum(sums.values()) >= busy, "per-op sums below the union"
    labelled = traces.label_gaps(traces.gaps(dev, lo, hi),
                                 traces.clip(host, lo, hi))
    assert sum(labelled.values()) == idle, (labelled, idle)
    print(f"ok: window {(hi - lo) / 1e6:.3f} ms, {len(dev)} operations, "
          f"busy {busy / 1e6:.3f} ms (grid {busy_hand / 1e6:.3f} ms), "
          f"idle {idle / 1e6:.3f} ms by host event "
          f"{ {k: round(v / 1e6, 3) for k, v in labelled.items()} }")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--record", action="store_true")
    args = ap.parse_args(argv)
    if args.record:
        record()
    check()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark of the fused decentralised-learning engine, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``)
and a traffic mix (``bench/traffic``); its correctness limits sit in
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, so a cell, a
configuration, a mix or a metric is added by adding files.

One run:

- set-up (``setup_s``): the compile cache, the experiment's inputs built
  once by the program's ``setup_experiment`` (corpus, shards, simulated
  cluster), the strategy, and one warm-up experiment identical to the
  timed ones;
- window: experiments back to back, each one call of
  ``core.fused.run_dfl_fused`` (the engine ``run_algorithm(fused=True)``
  dispatches to) on fresh copies of the pristine cluster and strategy,
  until ``--seconds`` have passed; the experiment that crosses the mark
  ends the window. ``round_ms`` is the window over the rounds done;
- ``peak_hbm_gib``: the HBM held at the peak on the fullest device (live
  buffers plus the reservation for program temporaries), read before any
  reference work;
- correctness: the plain reference (``bench/reference``) replays the
  last experiment from the seed under the plans the strategy issued,
  and the numbers of ``bench/compare.py`` are held to the cell's limits;
- ``--trace 1``: the window runs under the profiler and the per-layer
  metrics are read from the trace instead of the end-to-end ones.

The last line of standard output is the result as one JSON object.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

import numpy as np  # noqa: E402

GIB = float(1 << 30)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# cells, configurations, traffic: found by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    end_to_end: list[dict]
    per_layer: list[dict]   # the metrics whose workloads include the cell


def load_cell(name: str, manifest: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(manifest.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def model_spec(cell: Cell) -> str:
    """The program's model spec string: the configuration's, with the
    traffic's sequence length for a token model."""
    spec = cell.config["program_spec"]
    seq = cell.traffic.get("seq")
    return f"{spec},seq={seq}" if seq else spec


def fedhp_config(cell: Cell, seed: int):
    from repro.configs.base import FedHPConfig
    p = cell.traffic
    return FedHPConfig(
        algorithm=p["strategy"], num_workers=p["workers"],
        rounds=p["rounds"], tau_init=p["tau_init"], tau_max=p["tau_max"],
        lr=p["lr"], lr_decay=p["lr_decay"], batch_size=p["batch"],
        base_topology=p["base_topology"], replan_every=p["replan_every"],
        gossip=p["gossip"], compress=p["compress"], robust=p["robust"],
        sharded=p["mesh_chips"] > 0, seed=seed, model=model_spec(cell))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Recorder:
    """The strategy of one experiment, with every plan it issues and every
    Alg. 1 observation it receives kept for the reference. It delegates
    everything else, so the engine sees the strategy it would anyway."""

    def __init__(self, inner, on_plan=None):
        self.inner = inner
        self.on_plan = on_plan
        self.plans: list[tuple[np.ndarray, np.ndarray]] = []
        self.observed: list[dict] = []

    def __getattr__(self, key):
        return getattr(self.inner, key)

    def plan(self, h, alive=None):
        p = self.inner.plan(h, alive=alive)
        self.plans.append((np.array(p.taus), np.array(p.adj)))
        if self.on_plan is not None:
            self.on_plan(self.plans[-1][0])
        return p

    def observe(self, h, **kw):
        self.observed.append({k: np.array(kw[k], np.float64) for k in
                              ("edge_dist", "update_norms", "smooth_l",
                               "sigma", "loss")})
        return self.inner.observe(h, **kw)


@dataclass
class Inputs:
    cfg: object
    params: dict
    train: object
    test_x: np.ndarray
    test_y: np.ndarray
    shards: list
    cluster: object
    strategy: object
    mesh: object = None
    setup_parts: dict = field(default_factory=dict)


def build_inputs(cell: Cell, seed: int) -> Inputs:
    """The experiment's inputs, built once with the program's own
    ``setup_experiment``, ``make_base_topology`` and ``make_strategy``
    (exactly as ``run_algorithm`` builds them)."""
    from repro.core.algorithms import make_strategy
    from repro.core.experiment import setup_experiment
    from repro.core.topology import make_base_topology
    cfg = fedhp_config(cell, seed)
    p = cell.traffic
    t = time.perf_counter()
    train, tx, ty, shards, cluster = setup_experiment(
        cfg, non_iid_p=p["non_iid_p"], num_samples=p["num_samples"],
        spread=p["spread"], rounds=p["rounds"])
    parts = {"inputs_s": time.perf_counter() - t}
    base = make_base_topology(cfg.num_workers, cfg.base_topology, cfg.seed)
    mesh = None
    if p["mesh_chips"]:
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh(p["mesh_chips"])
    return Inputs(cfg=cfg, params=p, train=train, test_x=tx, test_y=ty,
                  shards=shards, cluster=cluster,
                  strategy=make_strategy(cfg, base), mesh=mesh,
                  setup_parts=parts)


def run_experiment(inp: Inputs, on_plan=None):
    """One experiment through the fused engine; blocks until its last
    round is on the host and its final parameters are on the device."""
    import jax
    from repro.core.fused import run_dfl_fused
    with jax.profiler.TraceAnnotation("bench.experiment_init"):
        rec = Recorder(copy.deepcopy(inp.strategy), on_plan)
        cluster = copy.deepcopy(inp.cluster)
    with jax.profiler.TraceAnnotation("bench.run_dfl_fused"):
        hist = run_dfl_fused(inp.train, inp.test_x, inp.test_y, inp.shards,
                             cluster, inp.cfg, rec,
                             rounds=inp.params["rounds"],
                             eval_subset=inp.params["eval_rows"],
                             mesh=inp.mesh)
        jax.block_until_ready(hist.final_params)
    return hist, rec


class CompileCounter:
    """Compilations and persistent-cache lookups, from JAX's monitoring
    events; ``window`` counts those that happen while it is set."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.compile_s: dict[str, float] = {}
        self.in_window = 0
        self.window = False
        self.label = "before the first plan"
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_dur(self, event: str, duration: float, **_):
        # one event per program compiled or loaded from the cache
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s[self.label] = \
                self.compile_s.get(self.label, 0.0) + duration
            if self.window:
                self.in_window += 1


def require_chips(n: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < n:
        print(f"bench: the cell needs {n} TPU chip(s); JAX found {dev}. "
              "There is no CPU fallback.", file=sys.stderr)
        sys.exit(2)
    return dev


def memory_peak_bytes(n: int) -> int:
    """HBM the run held at its peak on the fullest device: the allocator's
    peak of live buffers plus its peak reservation for the compiled
    programs' temporaries, which the TPU runtime keeps apart from the
    buffers (``peak_bytes_reserved``)."""
    import jax

    def held(d):
        s = d.memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0)) + \
            int(s.get("peak_bytes_reserved", 0))
    return max(held(d) for d in jax.devices()[:n])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float = T_START) -> dict:
    import jax
    from repro.launch.cache import enable_compile_cache

    import compare
    import traces

    compare.refuse_unmodelled(cell.traffic)
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    inp = build_inputs(cell, seed)

    # warm-up: every shape the window uses compiles (or loads) here; the
    # compile seconds are put down to the tau bucket of the latest plan
    def on_plan(taus):
        counter.label = f"tau bucket {1 << (int(max(taus.max(), 1)) - 1).bit_length()}"

    hist, rec = run_experiment(inp, on_plan)
    hist.final_params = None
    buckets = sorted({1 << (int(max(t.max(), 1)) - 1).bit_length()
                      for t, _ in rec.plans})
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: inputs (corpus, shards, cluster) "
        f"{inp.setup_parts['inputs_s']:.3f} s; compile cache {cache_dir}: "
        f"{counter.hits} hits, {counter.misses} misses; compile and cache "
        f"load seconds {counter.compile_s}; tau buckets {buckets}")

    counter.window = True
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    records, n_exp = [], 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            hist, rec = run_experiment(inp)
            n_exp += 1
            records.append([r.__dict__.copy() for r in hist.records])
            if time.perf_counter() - t0 >= seconds:
                break
            hist.final_params = None
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    counter.window = False
    rounds_done = sum(len(r) for r in records)
    peak = memory_peak_bytes(cell.chips)
    log(f"window {window_s:.3f} s: {n_exp} experiments, {rounds_done} "
        f"rounds; compiles or cache loads in the window: "
        f"{counter.in_window}; peak {peak} bytes; allocator "
        f"{jax.devices()[0].memory_stats()}")

    result = {"attempted": rounds_done, "device": dict(device)}
    result["device"]["memory_peak_bytes"] = peak
    if trace:
        run = traces.TraceRun.load(trace_dir.name, cell=cell, device=device,
                                   records=records, plans=rec.plans,
                                   eval_rows=min(inp.params["eval_rows"],
                                                 len(inp.test_x)),
                                   window_s=window_s,
                                   compiles=counter.in_window)
        trace_dir.cleanup()
        metrics = {}
        for m in cell.per_layer:
            value = load_metric(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"]["busy_s"] = run.busy_s
        result["device"]["window_s"] = run.window_s
        result["breakdown"] = run.breakdown()
    else:
        values = {"round_ms": window_s * 1000.0 / rounds_done,
                  "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # correctness: program state reduced, freed, then the reference
    t = time.perf_counter()
    readings, ref_s = compare.check(cell, inp, hist, rec, records)
    hist.final_params = None
    log(f"reference and comparison {time.perf_counter() - t:.3f} s; "
        f"reference by phase {ref_s}")
    for c in readings:
        if c["limit"] is None:
            log(f"reading {c['name']}: {c['value']!r} (no limit)")
    checks = [c for c in readings if c["limit"] is not None]
    failed = sum(1 for c in checks if not c["value"] <= c["limit"])
    result.update(correct=bool(checks) and failed == 0, failed=failed,
                  metrics=metrics)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})"
            f"{'' if c['value'] <= c['limit'] else '  FAILED'}")
    return result


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    device = require_chips(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device)
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = result["checks"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

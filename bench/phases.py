"""Split of a traced window by phase of the round program: an operator's
tool, not a benchmark metric.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

On the chip: set-up and one warm-up experiment as in ``bench/run.py``,
then a window of experiments under the profiler, and the split of that
window.

Both round programs (``core/fused._scan_segment`` and its sharded twin)
run each phase under a ``jax.named_scope`` (``dfl.join``,
``dfl.local_sgd``, ``dfl.codec``, ``dfl.robust``, ``dfl.mix``,
``dfl.evaluation``, ``dfl.alg1_measure``), which lands in the
``op_name`` metadata of each compiled instruction. A device operation's
trace event names its instruction but carries no metadata, so the split
reads the HLO protos that the profile keeps in its ``/host:metadata``
plane, one per program, and finds each operation's program from the
``XLA Modules`` event it runs in (``<module>(<program id>)``). An
operation belongs to the innermost ``dfl.*`` scope of its ``op_name``.

It prints, as one JSON object on the last line:

- ``busy_s`` by phase (loops, which hold their bodies' operations, left
  out, as in ``traces.TraceRun.breakdown``), the share of busy time under
  some scope, and the largest operations under none;
- ``peak_share``: each phase's required FLOPs (``phase_flops``, the terms
  of ``counts.round_flops``) over its device seconds times the bf16 peak;
- ``idle_s``: the first chip's idle time under each host span of
  ``run_dfl_fused`` (``spans.idle_under``);
- ``segment_ms``: the wall time of each ``dfl.segment`` step.

JAX keys its persistent compile cache without metadata, so a program
loaded from an entry that a build without these scopes wrote runs
without them. The tool therefore keys its own compiles with metadata
(``jax_compilation_cache_include_metadata_in_key``).
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

import counts
import spans
import traces

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
PHASES = ("dfl.join", "dfl.local_sgd", "dfl.codec", "dfl.robust",
          "dfl.mix", "dfl.evaluation", "dfl.alg1_measure")
NO_SCOPE = "(no dfl scope)"
NO_OP_NAME = "(no op_name)"
UNMAPPED = "(not in the profile's HLO)"


# ---------------------------------------------------------------------------
# the profile's HLO protos, read from the protobuf wire format
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _first(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def hlo_op_names(xplane: bytes) -> dict[int, dict[str, str]]:
    """Program id -> {instruction name: op_name} of every HLO proto the
    profile keeps (XSpace.planes -> XPlane ``/host:metadata``: one event
    metadata a program, whose ``Hlo Proto`` stat holds an HloProto)."""
    out: dict[int, dict[str, str]] = {}
    for f, plane in _fields(xplane):
        if f != 1 or bytes(_first(plane, 2, b"")) != METADATA_PLANE.encode():
            continue
        stat_ids = set()
        for g, entry in _fields(plane):
            if g == 5:                 # stat_metadata: {id: XStatMetadata}
                meta = _first(entry, 2)
                if bytes(_first(meta, 2, b"")) == b"Hlo Proto":
                    stat_ids.add(_first(meta, 1))
        for g, entry in _fields(plane):
            if g != 4:                 # event_metadata: {id: XEventMetadata}
                continue
            meta = _first(entry, 2)
            pid = _first(meta, 1)
            for h, stat in _fields(meta):
                if h == 5 and _first(stat, 1) in stat_ids:
                    out[pid] = _instruction_op_names(_first(stat, 6))
    return out


def _instruction_op_names(hlo_proto) -> dict[str, str]:
    """HloProto.hlo_module -> computations -> instructions: name -> the
    op_name of its OpMetadata (empty where it has none)."""
    names = {}
    module = _first(hlo_proto, 1)
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name, op_name = "", ""
            for h, v in _fields(instr):
                if h == 1:
                    name = bytes(v).decode()
                elif h == 7:
                    op_name = bytes(_first(v, 2, b"")).decode()
            names[name] = op_name
    return names


# ---------------------------------------------------------------------------
# device operations and their programs
# ---------------------------------------------------------------------------

def instruction(event_name: str) -> str:
    """``fusion.12`` of a chip's ``%fusion.12 = f32[...] fusion(...)``
    (and of the CPU runtime's plain ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(pd, lo: int, hi: int, chips: int) -> list:
    """(event, program id or None) of the window's device operations on
    the cell's first ``chips`` chips. Without a device plane (the CPU),
    the XLA runtime's host threads stand in, each event naming its
    program in a ``program_id`` stat."""
    out = []
    planes = sorted((p for p in pd.planes
                     if p.name.startswith(traces.DEVICE_PLANE)),
                    key=lambda p: p.name)[:chips]
    for plane in planes:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                       _program_id(e.name))
                      for e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        for e in lines.get(traces.OPS_LINE, []):
            s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
            k = bisect.bisect_right(starts, s) - 1
            pid = mods[k][2] if k >= 0 and s < mods[k][1] else None
            out.append((traces.Event(e.name, s, t), pid))
    if not planes:
        for plane in pd.planes:
            if not plane.name.startswith(traces.HOST_PLANE):
                continue
            for ln in plane.lines:
                if not ln.name.startswith("tf_XLA"):
                    continue
                for e in ln.events:
                    pid = dict(e.stats).get("program_id")
                    if pid is not None:
                        s = int(e.start_ns)
                        out.append((traces.Event(
                            e.name, s, s + int(e.duration_ns)), int(pid)))
    return [(traces.clip([e], lo, hi)[0], pid) for e, pid in out
            if e.end > lo and e.start < hi]


def _program_id(module_event: str):
    m = re.search(r"\((\d+)\)\s*$", module_event)
    return int(m.group(1)) if m else None


def scope_of(op_name: str) -> str:
    """The innermost ``dfl.*`` component of an op_name."""
    inner = [p for p in op_name.split("/") if p.startswith("dfl.")]
    return inner[-1] if inner else NO_SCOPE


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------

def split(ops: list, op_names: dict[int, dict[str, str]]) -> dict:
    """Busy seconds by phase, loops left out, with the largest operations
    that fall under no phase."""
    busy: dict[str, float] = {}
    rest: dict[str, float] = {}
    for e, pid in ops:
        name = instruction(e.name)
        if name.startswith("while"):
            continue
        table = op_names.get(pid, {})
        if name not in table:
            where = UNMAPPED
        elif not table[name]:
            where = NO_OP_NAME
        else:
            where = scope_of(table[name])
        sec = (e.end - e.start) / 1e9
        busy[where] = busy.get(where, 0.0) + sec
        if where not in PHASES:
            key = f"{name} :: {table.get(name) or where}"
            rest[key] = rest.get(key, 0.0) + sec
    total = sum(busy.values())
    scoped = sum(v for k, v in busy.items() if k in PHASES)
    return {"busy_s": busy, "busy_total_s": total,
            "scoped_share": 100.0 * scoped / total if total else None,
            "largest_unscoped": sorted(rest.items(), key=lambda kv: -kv[1])[:12]}


def phase_flops(model: dict, traffic: dict, taus_sum: float, links: int,
                eval_rows: int, measured: bool) -> dict[str, float]:
    """``counts.round_flops`` term by term, each under the scope of the
    phase that does the work; the terms sum to it."""
    w, seq = traffic["workers"], traffic["seq"]
    fwd = counts.forward_flops_per_row(model, seq)
    terms = {"dfl.local_sgd": 3 * fwd * traffic["batch"] * taus_sum,
             "dfl.evaluation": fwd * eval_rows * w,
             "dfl.mix": 2 * 2 * links * counts.param_count(model)}
    if measured:
        terms["dfl.alg1_measure"] = w * 3 * fwd * (2 * 256 * w + 32 * w)
    return terms


def window_phase_flops(cell, records, eval_rows: int) -> dict[str, float]:
    p = cell.traffic
    out: dict[str, float] = {}
    for exp in records:
        for r in exp:
            for k, v in phase_flops(cell.config, p,
                                    r["mean_tau"] * p["workers"],
                                    r["num_links"], eval_rows,
                                    p["strategy"] == "fedhp").items():
                out[k] = out.get(k, 0.0) + v
    return out


def peak_share(flops: dict[str, float], busy: dict[str, float],
               peak: float) -> dict[str, float]:
    """Each compute phase's required FLOPs over its device seconds (summed
    over the cell's chips) times one chip's peak, in %."""
    return {k: 100.0 * flops[k] / (busy[k] * peak)
            for k in ("dfl.local_sgd", "dfl.evaluation", "dfl.alg1_measure")
            if k in flops and busy.get(k, 0.0) > 0}


HOST_SPANS = ("dfl.init", "dfl.precompute", "dfl.plan", "dfl.upload",
              "dfl.dispatch", "dfl.sync", "dfl.observe")


def report(run, ops, op_names, cell, eval_rows: int) -> dict:
    """The split of one traced window (``run`` a ``traces.TraceRun``)."""
    out = split(ops, op_names)
    out["window_s"] = run.window_s
    out["programs_with_hlo"] = len(op_names)
    out["ops_without_program"] = sum(1 for _, pid in ops if pid is None)
    flops = window_phase_flops(cell, run.records, eval_rows)
    out["phase_tflop"] = {k: v / 1e12 for k, v in flops.items()}
    out["peak_share"] = peak_share(
        flops, out["busy_s"], counts.peaks(run.device["kind"])["bf16_flops"])
    out["idle_s"] = {n: spans.idle_under(run, n) / 1e9 for n in HOST_SPANS}
    out["idle_s"]["(window)"] = run.window_s - run.busy_s
    seg = sorted((e.end - e.start) / 1e6
                 for e in spans.named(run, "dfl.segment"))
    if seg:
        q = statistics.quantiles(seg, n=20) if len(seg) > 1 else seg * 19
        out["segment_ms"] = {"count": len(seg), "median": statistics.median(seg),
                             "p95": q[18], "max": seg[-1]}
    return out


# ---------------------------------------------------------------------------
# one window on the chip
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    import jax

    import run as bench_run
    from repro.launch.cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    device = bench_run.require_chips(cell.chips)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    enable_compile_cache()
    inp = bench_run.build_inputs(cell, args.seed)
    bench_run.run_experiment(inp)                      # warm-up
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    records = []
    with tempfile.TemporaryDirectory() as path:
        jax.profiler.start_trace(path, profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(traces.WINDOW):
            while True:
                hist, _ = bench_run.run_experiment(inp)
                records.append([r.__dict__.copy() for r in hist.records])
                hist.final_params = None
                if time.perf_counter() - t0 >= args.seconds:
                    break
        jax.profiler.stop_trace()
        eval_rows = min(inp.params["eval_rows"], len(inp.test_x))
        run = traces.TraceRun.load(path, cell=cell, device=device,
                                   records=records, plans=[],
                                   eval_rows=eval_rows, window_s=0.0,
                                   compiles=0)
        src = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                        recursive=True)[0]
        with open(src, "rb") as f:
            op_names = hlo_op_names(f.read())
        ops = device_ops(traces.load_profile(path), run.lo, run.hi,
                         cell.chips)
    print(json.dumps(report(run, ops, op_names, cell, eval_rows)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

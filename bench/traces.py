"""Reduction of a profiler trace to the benchmark's device metrics.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On a TPU each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event per device operation. The host plane
``/host:CPU`` holds the harness's own annotations (``bench.window``,
``bench.experiment_init``, ``bench.run_dfl_fused``) and what JAX's
runtime records on the host threads.

- busy: the union of a chip's operation intervals inside the window,
  averaged over the chips the cell uses;
- a kernel's time: the summed durations of the operations a reader's
  predicate picks. An operation's event is named by its whole HLO
  instruction; a Pallas kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"`` and no kernel name, so a
  reader picks it by its operand shapes;
- idle gaps: the stretches of the window in which a chip runs nothing,
  each put down to the harness annotation and the shortest host event
  that cover its middle.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

import counts

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"


@dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns


def load_profile(path: str):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def line_events(pd, plane_pred, line_pred) -> dict[str, list[Event]]:
    """Events of the matching lines, by plane name."""
    out: dict[str, list[Event]] = {}
    for plane in pd.planes:
        if not plane_pred(plane.name):
            continue
        evs = out.setdefault(plane.name, [])
        for line in plane.lines:
            if line_pred(line.name):
                evs.extend(Event(e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                           for e in line.events)
    return out


def clip(events: list[Event], lo: int, hi: int) -> list[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union_ns(events: list[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0, None, None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: list[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] that no event covers."""
    out, cur = [], lo
    for e in sorted(events, key=lambda e: e.start):
        if e.start > cur:
            out.append((cur, e.start))
        cur = max(cur, e.end)
    if cur < hi:
        out.append((cur, hi))
    return out


def short_name(hlo: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = f32[...] fusion(...)``, marked when
    the instruction is a Pallas kernel."""
    name = hlo.split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in hlo:
        name += " (tpu_custom_call)"
    return name


def by_name(events: list[Event]) -> dict[str, int]:
    tot: dict[str, int] = {}
    for e in events:
        tot[e.name] = tot.get(e.name, 0) + (e.end - e.start)
    return tot


def label_gaps(gap_list, host: list[Event]) -> dict[str, int]:
    """Idle ns by what the host was doing at each gap's middle: the
    innermost ``bench.*`` annotation covering it, and the shortest other
    host event covering it."""
    import numpy as np
    groups = []
    for ours in (True, False):
        evs = [h for h in host if h.name.startswith("bench.") == ours]
        groups.append((evs, np.array([h.start for h in evs], np.int64),
                       np.array([h.end for h in evs], np.int64)))
    out: dict[str, int] = {}
    for s, e in gap_list:
        mid = (s + e) // 2
        parts = []
        for evs, starts, ends in groups:
            hit = np.nonzero((starts <= mid) & (ends > mid))[0]
            if hit.size:
                parts.append(evs[hit[np.argmin(ends[hit] - starts[hit])]].name)
        name = " / ".join(parts) or "(no host event)"
        out[name] = out.get(name, 0) + (e - s)
    return out


@dataclass
class TraceRun:
    """What a metric reader reads: the traced window's device operations,
    the window's records and plans, and the cell."""
    cell: object
    device: dict
    records: list           # per experiment, per round: record dicts
    plans: list             # the last experiment's (taus, adj) per round
    eval_rows: int
    window_s: float
    busy_s: float
    compiles: int
    ops: dict = field(default_factory=dict)    # plane -> [Event] in window
    host: list = field(default_factory=list)   # host events in window
    lo: int = 0
    hi: int = 0

    @classmethod
    def load(cls, path, *, cell, device, records, plans, eval_rows,
             window_s, compiles):
        pd = load_profile(path)
        host = [e for evs in line_events(
            pd, lambda n: n.startswith(HOST_PLANE), lambda n: True).values()
            for e in evs]
        win = [e for e in host if e.name == WINDOW]
        if not win:
            raise ValueError(f"the trace holds no {WINDOW!r} annotation")
        lo, hi = win[0].start, win[0].end
        ops = line_events(pd, lambda n: n.startswith(DEVICE_PLANE),
                          lambda n: n == OPS_LINE)
        ops = {k: clip(v, lo, hi) for k, v in sorted(ops.items())}
        ops = dict(list(ops.items())[:cell.chips])
        if not ops or not any(ops.values()):
            raise ValueError("the trace holds no device operation in the "
                             "window")
        busy = sum(union_ns(v) for v in ops.values()) / len(ops) / 1e9
        return cls(cell=cell, device=device, records=records, plans=plans,
                   eval_rows=eval_rows, window_s=(hi - lo) / 1e9,
                   busy_s=busy, compiles=compiles, ops=ops,
                   host=clip(host, lo, hi), lo=lo, hi=hi)

    # -- what readers use --------------------------------------------------
    def kernel(self, pick) -> tuple[int, float]:
        """(calls, seconds) of the device operations whose HLO text
        ``pick`` accepts, summed over the cell's chips."""
        evs = [e for v in self.ops.values() for e in v if pick(e.name)]
        return len(evs), sum(e.end - e.start for e in evs) / 1e9

    def peaks(self) -> dict:
        return counts.peaks(self.device["kind"])

    def window_flops(self) -> float:
        """FLOPs the window's experiments required (``counts``)."""
        p = self.cell.traffic
        measured = p["strategy"] == "fedhp"
        total = 0.0
        for exp in self.records:
            for r in exp:
                total += counts.round_flops(
                    self.cell.config, p, r["mean_tau"] * p["workers"],
                    r["num_links"], self.eval_rows, measured)
        return total

    def breakdown(self) -> dict:
        """The operations that took most device time (loops, which hold
        the operations of their bodies, left out) and the longest idle
        stretches by what the host was doing."""
        ops = by_name([Event(short_name(e.name), e.start, e.end)
                       for v in self.ops.values() for e in v
                       if not e.name.startswith("%while")])
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        first = next(iter(self.ops.values()))
        idle = label_gaps(gaps(first, self.lo, self.hi), self.host)
        longest = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, t / 1e9] for n, t in top],
                "idle_gaps": [[n, t / 1e9] for n, t in longest]}

"""The host control plane of ``run_dfl_fused``, read from the program's own
profiler spans.

``run_dfl_fused`` wraps its control plane in ``jax.profiler``
annotations: ``dfl.init`` (parameters and evaluation stacks), one
``dfl.segment`` step a scan segment (``step_num`` its first round) and,
inside it, ``dfl.precompute`` (holding one ``dfl.plan`` per
``strategy.plan`` call), ``dfl.upload``, ``dfl.dispatch``, ``dfl.sync``
and ``dfl.observe``, each with the segment's first round ``h``. The
trace's host plane holds them by name, on the device trace's clock.

A program without these spans yields none, and every reader built on
this module then returns None.
"""
from __future__ import annotations

import traces


def named(run, *names: str) -> list:
    """The window's host spans of the given names."""
    return [e for e in run.host if e.name in names]


def idle_under(run, *names: str) -> int:
    """Nanoseconds of the window in which the cell's first chip runs
    nothing while the host is inside one of the named spans: |G & S| =
    |G| + |S| - |G | S| for the chip's idle stretches G and the spans S."""
    first = next(iter(run.ops.values()))
    idle = [traces.Event("", s, e)
            for s, e in traces.gaps(first, run.lo, run.hi)]
    held = named(run, *names)
    return (traces.union_ns(idle) + traces.union_ns(held)
            - traces.union_ns(idle + held))

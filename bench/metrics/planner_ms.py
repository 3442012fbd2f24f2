"""Host milliseconds a round spends planning and observing: the window's
time inside the ``dfl.plan`` and ``dfl.observe`` spans of
``run_dfl_fused`` over the rounds done in the window. Layer: host
control plane."""

import spans
import traces


def read(run):
    rounds = sum(len(exp) for exp in run.records)
    evs = spans.named(run, "dfl.plan", "dfl.observe")
    if not evs or rounds == 0:
        return None
    return traces.union_ns(evs) / 1e6 / rounds

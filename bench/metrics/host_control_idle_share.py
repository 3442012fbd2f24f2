"""Share of the traced window in which the cell's first chip runs nothing
while the host is inside the control plane of ``run_dfl_fused``
(``dfl.init``, ``dfl.precompute``, ``dfl.upload``, ``dfl.dispatch`` or
``dfl.observe``): the chip's idle stretches intersected with the union of
those spans, over the window. Layer: host control plane."""

import spans

CONTROL = ("dfl.init", "dfl.precompute", "dfl.upload", "dfl.dispatch",
           "dfl.observe")


def read(run):
    if run.hi <= run.lo or not spans.named(run, *CONTROL):
        return None
    return 100.0 * spans.idle_under(run, *CONTROL) / (run.hi - run.lo)

"""Roofline share of the edge-list gossip kernel (``gossip_edges``): the
least time its HBM bytes need at the chip's bandwidth (read [W, P] and
the three edge tables once, write [W, P] once) over the kernel's summed
device time. The kernel is the Pallas call (``tpu_custom_call``) whose
operands are the int32 source and destination tables, the float32
weights and the [W, P] rows as they lie, unpadded. Layer: gossip
kernels."""

import re

import counts

SIG = re.compile(r"custom-call\(s32\[(\d+)\][^,]*, s32\[\1\][^,]*, "
                 r"f32\[\1\][^,]*, f32\[(\d+),(\d+)\]")


def read(run):
    p = run.cell.traffic
    w, size = p["workers"], counts.param_count(run.cell.config)
    edges = []

    def pick(hlo):
        m = SIG.search(hlo)
        if 'custom_call_target="tpu_custom_call"' not in hlo or m is None:
            return False
        edges.append(int(m.group(1)))
        return True

    calls, seconds = run.kernel(pick)
    if calls == 0 or seconds <= 0:
        return None
    least = sum(counts.mix_edges_bytes(w, size, e) for e in edges)
    return 100.0 * least / run.peaks()["hbm_bytes_per_s"] / seconds

"""Roofline share of the dense gossip kernel (``gossip_mix_rows``): the
least time its HBM bytes need at the chip's bandwidth (read [W, P] and
the [W, W] mix once, write [W, P] once; its FLOPs bound it far less)
over the kernel's summed device time. The kernel is the Pallas call
(``tpu_custom_call``) whose operands are ``f32[W,W]`` and ``f32[W,P]``.
Layer: gossip kernels."""

import counts


def read(run):
    p = run.cell.traffic
    w, size = p["workers"], counts.param_count(run.cell.config)
    sig = f"custom-call(f32[{w},{w}]"

    def pick(hlo):
        return 'custom_call_target="tpu_custom_call"' in hlo and sig in hlo \
            and f"f32[{w},{size}]" in hlo

    calls, seconds = run.kernel(pick)
    if calls == 0 or seconds <= 0:
        return None
    least = calls * counts.mix_rows_bytes(w, size)
    return 100.0 * least / run.peaks()["hbm_bytes_per_s"] / seconds

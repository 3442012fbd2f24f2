"""The whole round program's share of the chip's bf16 peak: the FLOPs the
window's experiments required (``counts.round_flops``: local SGD at the
planned taus, evaluation, mixing and Alg. 1 measurement) over window
seconds x chips x peak. Layer: round program."""


def read(run):
    flops = run.window_flops()
    if flops <= 0 or run.window_s <= 0:
        return None
    return 100.0 * flops / (run.window_s * run.cell.chips
                            * run.peaks()["bf16_flops"])

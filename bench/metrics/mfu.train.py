"""Model FLOP utilization of the edge round, in percent: the model FLOPs
of the traced rounds (``bench/flops/<config>.py``) over the traced
window's seconds times chips times the device's bf16 peak
(``bench/peaks.json``)."""


def read(out):
    if out.trace is None or not out.rounds_traced or not out.flops_per_round:
        return None
    flops = out.flops_per_round * out.rounds_traced
    return 100.0 * flops / (out.trace.window_s * out.chips
                            * out.peaks["bf16_flops_per_s"])

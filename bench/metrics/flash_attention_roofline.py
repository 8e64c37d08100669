"""The flash attention kernel's share of its roofline, in percent: the
least time the chip could take for the work its calls in the traced
window needed (``bench/rooflines/flash_attention.py``), the larger of
FLOPs over the bf16 peak and bytes over the HBM peak
(``bench/peaks.json``), over the self time of the kernel's own
operations (``bench/kinds/edge_round_layers.py``)."""


def read(out):
    secs = getattr(out, "kernel_s", None)
    if not secs or out.peaks is None:
        return None
    least = max(out.kernel_flops / out.peaks["bf16_flops_per_s"],
                out.kernel_bytes / out.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs

"""Model FLOP utilization of the whole served step, in percent: the model
FLOPs of every prefill and decode step that finished in the traced window,
from the configuration's published sizes (``bench/flops.py``), over the
window's seconds times the chip's bf16 peak."""


def read(ctx):
    flops = ctx.records["model_flops_in_trace"]
    if not flops:
        return None
    return 100.0 * flops / (ctx.devices[0].window_s * ctx.peaks["bf16_flops_per_s"])

"""Roofline share of the flash-attention Pallas kernel
(``kernels/flash_attention.py``) in the prefills of the traced window, in
percent: per call the larger of its causal FLOPs over the bf16 peak and its
q, k, v and output bytes over HBM bandwidth, from the call's own shapes,
summed over calls and divided by the kernel's device time."""

from bench import flops
from bench.xtrace import roofline_share, shapes


def read(ctx):
    work, secs = [], 0.0
    for e, _ in ctx.devices[0].kernel_events("flash_attention"):
        _, q, k = shapes(e.name)[:3]
        work.append(flops.flash_attention_work(q[1], k[1], causal=True))
        secs += e.dur_ns * 1e-9
    share = roofline_share(work, secs, ctx.peaks)
    return None if share is None else share[0]

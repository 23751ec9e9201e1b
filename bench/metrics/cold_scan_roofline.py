"""Roofline share of the cold-scan Pallas kernel (``kernels/cold_scan.py``),
in percent: per call the code plane in and the mask out over every
(seed, placement) row and request, unpadded, at the chip's HBM bandwidth,
over the kernel's device time in the traced window."""

from bench import flops
from bench.xtrace import roofline_share


def read(ctx):
    calls = ctx.devices[0].kernel_events("cold_scan")
    work = flops.cold_scan_work(ctx.records["rows"], ctx.records["n_requests"])
    share = roofline_share([work] * len(calls), sum(e.dur_ns for e, _ in calls) * 1e-9,
                           ctx.peaks)
    return None if share is None else share[0]

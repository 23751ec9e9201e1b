"""Roofline share of the cold-scan Pallas kernel (``kernels/cold_scan.py``),
in percent. The work of one sweep is the code plane in and the mask out
over every node, (seed, placement) row and request, unpadded, at the
chip's HBM bandwidth; it is spread evenly over the kernel calls that one
whole execution of the sweep program (``jit__sweep``) makes in the trace,
however the program groups nodes or rows into calls. The share is that
work over the kernel's device time in the traced window; with no whole
sweep or no kernel call in the window there is nothing to read."""

from bench import flops
from bench.xtrace import module_name, roofline_share


def read(ctx):
    dev = ctx.devices[0]
    calls = dev.kernel_events("cold_scan")
    sweeps = [m for m in dev.modules if module_name(m.name) == "jit__sweep"
              and dev.lo <= m.start_ns and m.end_ns <= dev.hi]
    if not calls or not sweeps:
        return None
    one = sweeps[0]
    per_sweep = sum(1 for e, _ in calls if one.start_ns <= e.start_ns <= one.end_ns)
    if not per_sweep:
        return None
    r = ctx.records
    _, nbytes = flops.cold_scan_work(r["nodes"] * r["rows"], r["n_requests"])
    share = roofline_share([(0.0, nbytes / per_sweep)] * len(calls),
                           sum(e.dur_ns for e, _ in calls) * 1e-9, ctx.peaks)
    return None if share is None else share[0]

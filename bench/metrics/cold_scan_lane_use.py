"""Share of the cold-scan kernel's lanes that carry a row, in percent, as
the chip ran the kernel: one sweep's (seed, placement) rows x workflow
nodes over the lanes of the kernel calls that one whole execution of the
sweep program (``jit__sweep``) makes in the trace, each call's lanes the
last dimension of the code plane it was handed (its operand's shape in the
instruction's text); the median over the whole sweeps in the traced
window. With no whole sweep, no kernel call in the window, or a call
whose text names no operand shape, there is nothing to read."""

import statistics

from bench.xtrace import module_name, shapes


def read(ctx):
    dev = ctx.devices[0]
    calls = dev.kernel_events("cold_scan")
    shares = []
    for one in dev.modules:
        if module_name(one.name) != "jit__sweep" or not (
            dev.lo <= one.start_ns and one.end_ns <= dev.hi
        ):
            continue
        planes = [shapes(e.name)[1:2] for e, _ in calls
                  if one.start_ns <= e.start_ns <= one.end_ns]
        lanes = [p[0][1][-1] for p in planes if p and p[0][1]]
        if lanes and len(lanes) == len(planes):
            rows = ctx.records["rows"] * ctx.records["nodes"]
            shares.append(100.0 * rows / sum(lanes))
    return statistics.median(shares) if shares else None

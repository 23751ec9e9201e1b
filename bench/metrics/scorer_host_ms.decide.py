"""Host time per decision of the control plane (``adapt/scorer.py``, the
host side of ``core/jaxsim.py``): the median over the decisions wholly
inside the traced window of the decision's span less the device time of
its sweep, in ms. A sweep belongs to the decision whose span holds the
middle of its execution."""

import statistics

from bench.xtrace import module_name


def read(ctx):
    dev = ctx.devices[0]
    sweeps = [m for m in dev.modules if module_name(m.name) == "jit__sweep"]
    host = []
    for s in ctx.spans:
        if s.name != "bench.decision" or s.start_ns < dev.lo or s.end_ns > dev.hi:
            continue
        mine = [
            m for m in sweeps if s.start_ns <= m.start_ns + m.dur_ns / 2 <= s.end_ns
        ]
        if len(mine) == 1:
            host.append((s.dur_ns - mine[0].dur_ns) * 1e-6)
    return statistics.median(host) if host else None

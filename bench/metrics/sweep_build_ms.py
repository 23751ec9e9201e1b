"""Host time per call of the sweep's build (``core/simulator.py``
``simulate_placements`` and ``core/jaxsim.py`` ``run_batched`` up to the
dispatch: the graph, ``_build``'s arrays, the key layout): the median of
the program's ``geoff.sweep.build`` spans, in ms."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms("geoff.sweep.build")

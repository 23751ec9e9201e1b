"""Host time per call of the sweep's dispatch (``core/jaxsim.py``
``run_batched``: the ``_sweep`` call until it returns, its arguments'
transfer included): the median of the program's ``geoff.sweep.dispatch``
spans, in ms."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms("geoff.sweep.dispatch")

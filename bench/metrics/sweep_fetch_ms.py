"""Host time per call of the sweep's fetch (``core/jaxsim.py``
``run_batched``: the ready totals copied to host memory, failed requests
marked): the median of the program's ``geoff.sweep.fetch`` spans, in ms."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms("geoff.sweep.fetch")

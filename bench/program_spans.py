"""Readers of the program's own spans (``repro.obs.span``, the
``geoff.*`` phases of the control plane): durations come from the
program's in-memory ring, which holds the spans finished while the
profiler's session recorded (the traced window and the second before it).
The same spans are on the profiler's trace, but the harness hands readers
only the ``bench.`` host spans of it."""

import statistics


def median_ms(name: str):
    """Median duration of the program spans named ``name``, in ms; None when
    the ring holds none, or the program records no program spans."""
    try:
        from repro.obs import program_spans
    except ImportError:  # a program from before the program spans
        return None
    durations = [s.duration_s for s in program_spans(name)]
    return statistics.median(durations) * 1e3 if durations else None

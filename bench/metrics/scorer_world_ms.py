"""Host time per decision of the scorer's world build (``adapt/scorer.py``
``PlacementScorer.distributions``: platforms, steps through every
``compute_s``/``fetch_s`` callback, the cost simulator, the experiment):
the median of the program's ``geoff.scorer.world`` spans, in ms."""

from bench.program_spans import median_ms


def read(ctx):
    return median_ms("geoff.scorer.world")

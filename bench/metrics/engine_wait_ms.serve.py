"""Median per request of the seconds its critical path spent not computing
(poke slack, cold, fetch, transfer, stream wait), from the ``repro.obs``
tracer attached to the deployment (``dag/engine.py``,
``core/choreographer.py``), in ms."""

import statistics


def read(ctx):
    waits = ctx.records["engine_wait_s"]
    return statistics.median(waits) * 1e3 if waits else None

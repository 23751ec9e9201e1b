"""repro.obs — per-request tracing, histogram metrics, critical-path
attribution, SLOs, tail sampling, causal profiling, and Perfetto export.

The observability layer over the GeoFF engine and simulator. Level 1
(PR 7) sees: a ``Tracer`` collects per-request span trees from the real
DAG engine and all three simulator backends in one schema,
``MetricsRegistry`` keeps bounded log-bucketed latency histograms,
``extract_critical_path`` attributes end-to-end latency to
cold/fetch/compute/transfer/stream-wait/poke-slack, and
``write_chrome_trace`` exports Perfetto JSON. Level 2 (this layer) acts:
``WindowedHistogram`` turns quantiles time-local ("p95 over the last N
seconds"), ``SloSpec``/``SloTracker`` evaluate multi-window burn rates
and emit ``slo.burn`` events, ``TailSampler`` keeps only the traces worth
debugging (slow / SLO-violating / head-sampled), and
``calibrate``/``WhatIfProfiler`` replay observed traces with virtual
speedups to rank what to fix next — advice the recomposition controller
closes the loop on (``trigger="slo"``).

``instrument(deployment)`` wires a tracer into a live deployment the same
way ``repro.adapt.attach`` wires telemetry.

``span`` times the control plane's own phases (``geoff.scorer.*``,
``geoff.sweep.*``) while a ``jax.profiler`` session records: into the
profiler's trace and into a ring that ``program_spans`` reads.
"""

from repro.obs.critical_path import (
    BUCKETS,
    CriticalPath,
    Segment,
    extract_critical_path,
)
from repro.obs.metrics import LogHistogram, MetricsRegistry, WindowedHistogram
from repro.obs.perfetto import to_chrome_trace, write_chrome_trace
from repro.obs.profiler import (
    CalibratedWorkflow,
    Intervention,
    WhatIfProfiler,
    calibrate,
    profile_trace,
)
from repro.obs.sampler import TailSampler
from repro.obs.slo import SloSpec, SloTracker
from repro.obs.trace import (
    Span,
    Trace,
    Tracer,
    clear_program_spans,
    instrument,
    program_spans,
    span,
)

__all__ = [
    "BUCKETS",
    "CalibratedWorkflow",
    "CriticalPath",
    "Intervention",
    "LogHistogram",
    "MetricsRegistry",
    "Segment",
    "SloSpec",
    "SloTracker",
    "Span",
    "TailSampler",
    "Trace",
    "Tracer",
    "WhatIfProfiler",
    "WindowedHistogram",
    "calibrate",
    "clear_program_spans",
    "extract_critical_path",
    "instrument",
    "profile_trace",
    "program_spans",
    "span",
    "to_chrome_trace",
    "write_chrome_trace",
]

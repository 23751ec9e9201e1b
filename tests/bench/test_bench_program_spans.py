"""The readers of the program's own spans: each returns the median duration,
in ms, of its span among those the program's ring holds, and None when the
ring holds none of it."""

import os
import sys
from collections import deque

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import reader  # noqa: E402
from repro.obs import trace  # noqa: E402

READS = {
    "scorer_world_ms.decide": "geoff.scorer.world",
    "sweep_build_ms.decide": "geoff.sweep.build",
    "sweep_build_ms.throughput": "geoff.sweep.build",
    "sweep_dispatch_ms.decide": "geoff.sweep.dispatch",
    "sweep_dispatch_ms.throughput": "geoff.sweep.dispatch",
    "sweep_fetch_ms.decide": "geoff.sweep.fetch",
    "sweep_fetch_ms.throughput": "geoff.sweep.fetch",
}


def made(name, t_start, dur_s):
    s = trace.Span(len(name), "p1", None, name, "program", t_start, {})
    s.end(t_start + dur_s)
    return s


@pytest.mark.parametrize("metric", READS)
def test_reader_is_the_median_of_its_spans_in_ms(metric, monkeypatch):
    name = READS[metric]
    ring = deque(
        [made(name, 10.0, 0.004), made("geoff.sweep.wait", 10.1, 9.0)]
        + [made(name, 11.0 + i, d) for i, d in enumerate((0.001, 0.002, 0.030))]
        + [made(name + "x", 20.0, 5.0)]
    )
    monkeypatch.setattr(trace, "_program_ring", ring)
    assert reader(metric).read(None) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", READS)
def test_reader_gives_none_with_no_span_of_its_name(metric, monkeypatch):
    monkeypatch.setattr(trace, "_program_ring", deque())
    assert reader(metric).read(None) is None
    monkeypatch.setattr(trace, "_program_ring", deque([made("geoff.other", 1.0, 1.0)]))
    assert reader(metric).read(None) is None

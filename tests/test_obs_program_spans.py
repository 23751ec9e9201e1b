"""Program spans (``repro.obs.span``) on the scorer and the sweep: nothing
is recorded without a profiler session; under one, a scored decision
records the ``geoff.scorer`` and ``geoff.sweep`` phases with their parents,
shared ``trace_id``, counters, and names on the profiler's host plane, and
the totals stay bit-identical."""

import glob
import threading

import jax
import numpy as np
import pytest

from repro.adapt import PlacementScorer
from repro.core import PlacementCosts, jaxsim
from repro.core import simulator as S
from repro.obs import Tracer, clear_program_spans, program_spans, span
from repro.obs import trace as obs_trace

STEPS = ["check", "virus", "ocr", "e_mail"]
PLATS = ["edge", "gcf", "aws_us", "aws_eu"]
N_REQUESTS, SEEDS = 64, (3, 11)

# span -> its parent in one decision
PARENT = {
    "geoff.scorer": None,
    "geoff.scorer.world": "geoff.scorer",
    "geoff.sweep": "geoff.scorer",
    "geoff.sweep.build": "geoff.sweep",
    "geoff.sweep.dispatch": "geoff.sweep",
    "geoff.sweep.wait": "geoff.sweep",
    "geoff.sweep.fetch": "geoff.sweep",
    "geoff.scorer.collect": "geoff.scorer",
}


def decide():
    costs = PlacementCosts(
        fetch_s=lambda n, p, deps: 0.1 + 0.02 * STEPS.index(n),
        compute_s=lambda n, p: 0.2 + 0.05 * PLATS.index(p),
        transfer_s=lambda a, b, size: 0.0 if a == b else 0.05,
        payload_size=1000,
    )
    scorer = PlacementScorer(n_requests=N_REQUESTS, backend="jax", seeds=SEEDS)
    nodes = {n: None for n in STEPS}
    cands = [dict(zip(STEPS, ["edge"] + [p] * 3)) for p in PLATS]
    return scorer.distributions(nodes, list(zip(STEPS, STEPS[1:])), cands, costs)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One decision with no session, one under ``jax.profiler.trace``: the
    totals of both, the spans the second recorded, the bytes handed to and
    read back from the sweep, and the written trace's path."""
    clear_program_spans()
    untraced = decide()
    spans_off = program_spans()
    seen = {}
    real = jaxsim._sweep

    def sweep(*args, **kwargs):
        out = real(*args, **kwargs)
        seen["in"] = sum(a.nbytes for a in jax.tree.leaves(args))
        seen["out"] = sum(a.nbytes for a in jax.tree.leaves(out))
        return out

    path = tmp_path_factory.mktemp("profile")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jaxsim, "_sweep", sweep)
        with jax.profiler.trace(str(path)):
            totals = decide()
    spans = program_spans()
    clear_program_spans()
    (xplane,) = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    return {
        "untraced": untraced,
        "spans_off": spans_off,
        "totals": totals,
        "spans": {s.name: s for s in spans},
        "names": [s.name for s in spans],
        "bytes": seen,
        "xplane": xplane,
    }


def test_no_profiler_session_records_nothing(traced):
    assert traced["spans_off"] == []


def test_a_decision_records_the_eight_spans_with_their_parents(traced):
    assert sorted(traced["names"]) == sorted(PARENT)
    spans = traced["spans"]
    root = spans["geoff.scorer"]
    for name, parent in PARENT.items():
        s = spans[name]
        assert s.kind == "program" and s.trace_id == root.trace_id
        want = None if parent is None else spans[parent].span_id
        assert s.parent_id == want, name


def test_children_lie_inside_their_parents_and_tile_the_sweep(traced):
    spans = traced["spans"]
    for name, parent in PARENT.items():
        if parent is not None:
            s, p = spans[name], spans[parent]
            assert p.t_start <= s.t_start <= s.t_end <= p.t_end, name
    phases = [
        spans[f"geoff.sweep.{p}"] for p in ("build", "dispatch", "wait", "fetch")
    ]
    assert all(a.t_end <= b.t_start for a, b in zip(phases, phases[1:]))
    sweep = spans["geoff.sweep"]
    assert sweep.t_start <= phases[0].t_start and phases[-1].t_end <= sweep.t_end


def test_counters_are_the_work_and_the_arrays_nbytes(traced):
    spans, seen = traced["spans"], traced["bytes"]
    assert spans["geoff.scorer"].attrs == {
        "placements": len(PLATS), "seeds": len(SEEDS), "requests": N_REQUESTS,
        "backend": "jax",
    }
    assert spans["geoff.sweep"].attrs == {
        "requests": N_REQUESTS, "rows": len(SEEDS) * len(PLATS),
    }
    assert spans["geoff.sweep.build"].attrs == {"host_bytes": seen["in"]}
    assert spans["geoff.sweep.fetch"].attrs == {"fetched_bytes": seen["out"]}
    assert seen["out"] == traced["totals"].nbytes


def test_span_names_are_on_a_host_plane_of_the_profilers_trace(traced):
    from jax.profiler import ProfileData

    on_host = {
        e.name
        for p in ProfileData.from_file(traced["xplane"]).planes
        if p.name.startswith("/host:")
        for ln in p.lines
        for e in ln.events
    }
    assert set(PARENT) <= on_host


def test_totals_are_bit_identical_with_the_profiler_on_and_off(traced):
    a, b = traced["untraced"], traced["totals"]
    assert a.dtype == b.dtype == np.float32 and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def test_sweep_traces_span_on_the_tracer_path(tmp_path):
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    steps = [
        S.SimStep(n, p, compute=S.Dist(0.2, 0.1), fetch=S.Dist(0.05, 0.1))
        for n, p in zip(STEPS, ["tinyfaas-edge"] + ["gcf"] * 3)
    ]
    spec = S.ExperimentSpec(steps, n_requests=16, tracer=Tracer(sample=2))
    clear_program_spans()
    with jax.profiler.trace(str(tmp_path)):
        sim.simulate(spec, backend="jax")
    spans = {s.name: s for s in program_spans()}
    clear_program_spans()
    sweep, traces = spans["geoff.sweep"], spans["geoff.sweep.traces"]
    assert traces.parent_id == sweep.span_id and sweep.parent_id is None
    assert spans["geoff.sweep.fetch"].t_end <= traces.t_start <= traces.t_end
    assert traces.t_end <= sweep.t_end


def test_spans_nest_per_thread_and_end_once(tmp_path):
    clear_program_spans()
    got = {}

    def other():
        with span("geoff.other") as s:
            got["other"] = s.record

    with jax.profiler.trace(str(tmp_path)):
        with span("geoff.a") as a:
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=30)
            b = span("geoff.b")
            b.end()
            b.end()  # a second end changes nothing
            with span("geoff.c") as c:
                pass
    assert not t.is_alive()
    spans = program_spans()
    clear_program_spans()
    assert [s.name for s in spans].count("geoff.b") == 1
    assert got["other"].parent_id is None  # another thread's root
    assert got["other"].trace_id != a.record.trace_id
    assert b.record.parent_id == a.record.span_id
    assert c.record.parent_id == a.record.span_id  # b ended: a is current again
    assert getattr(obs_trace._program_tls, "span", None) is None


@pytest.mark.parametrize("n_seeds,n_placements,lanes", [(1, 1, 128), (10, 13, 256)])
def test_sweep_span_counts_the_kernel_lanes_on_the_pallas_path(
    tmp_path, monkeypatch, n_seeds, n_placements, lanes
):
    """With the Pallas cold scan (interpret mode here) every (seed,
    placement) row joins one kernel call per topological level (the Fig-4
    chain: four levels of one node, scanned as one run whose join reads
    one in-edge slot per node, the source's masked), whose lane width the
    ``geoff.sweep`` span counts: whole 128-lane blocks."""
    monkeypatch.setattr(jaxsim, "use_pallas", lambda: True)
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    fig4 = S.document_workflow_fig4()
    spec = S.ExperimentSpec(fig4, n_requests=16, seeds=tuple(range(n_seeds)))
    clear_program_spans()
    with jax.profiler.trace(str(tmp_path)):
        out = sim.simulate_placements(spec, [fig4] * n_placements)
    (sweep,) = program_spans("geoff.sweep")
    clear_program_spans()
    assert out.shape == (n_seeds, n_placements, 16)
    assert sweep.attrs == {
        "requests": 16, "rows": n_seeds * n_placements, "kernel_lanes": lanes,
        "levels": 4, "edges": 4, "kernel_lanes_total": 4 * lanes,
    }


@pytest.mark.parametrize(
    "n_seeds,n_placements,lanes,total", [(1, 1, 128, 384), (2, 4, 512, 768)]
)
def test_sweep_span_counts_levels_edges_and_lanes_of_a_wide_dag(
    tmp_path, monkeypatch, n_seeds, n_placements, lanes, total
):
    """The 1000Genome workflow of one chromosome: 64 nodes in three levels
    (49 sources, the merge, 14 sinks) and 76 edges. Each level's call
    holds rows x its nodes in whole 128-lane blocks: at 2 x 4 rows 392,
    8 and 112 rows in 512 + 128 + 128 lanes, two thirds of them used."""
    from repro.dag import genome_dag_1chr

    monkeypatch.setattr(jaxsim, "use_pallas", lambda: True)
    steps, edges = genome_dag_1chr()
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    spec = S.ExperimentSpec(steps, edges=edges, n_requests=16,
                            seeds=tuple(range(n_seeds)))
    clear_program_spans()
    with jax.profiler.trace(str(tmp_path)):
        sim.simulate_placements(spec, [steps] * n_placements)
    (sweep,) = program_spans("geoff.sweep")
    clear_program_spans()
    rows = n_seeds * n_placements
    assert sweep.attrs == {
        "requests": 16, "rows": rows, "kernel_lanes": lanes,
        "levels": 3, "edges": 76, "kernel_lanes_total": total,
    }
    if rows == 8:
        assert rows * 64 / total == pytest.approx(2 / 3)

"""The jax simulation backend: bit-equality with the numpy backend wherever
randomness cancels (sigma-0, with and without drift, chains and DAGs, cold
regimes), statistical equivalence where it doesn't (its draws come from
jax.random, not the numpy Generator), the CRN property across batched
placements, its own frozen draw-contract reference, and the guard rails."""

import math
from dataclasses import replace

import numpy as np
import pytest

from repro.core import simulator as S
from repro.core.faults import FaultEvent, FaultSchedule, OutageEvent, RetryPolicy
from repro.dag import document_dag_fig4, genome_dag_1chr

ATOL = 1e-9  # sigma-0 gap budget: reassociated float ops, not different math


def _zero_sigma(steps):
    return [
        replace(s, compute=S.Dist(s.compute.median, 0.0),
                fetch=S.Dist(s.fetch.median, 0.0))
        for s in steps
    ]


def _zero_platforms(keep_warm=None):
    return [
        replace(p, cold_start=S.Dist(p.cold_start.median, 0.0),
                **({} if keep_warm is None else {"keep_warm_s": keep_warm}))
        for p in S.paper_platforms()
    ]


def _both(sim, spec):
    a = sim.simulate(spec, backend="numpy")
    b = sim.simulate(spec, backend="jax")
    return a, b


# ---------------------------------------------------------------------------
# sigma-0: identical arithmetic, so the backends must agree to float noise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prefetch", [True, False])
@pytest.mark.parametrize(
    "make_steps",
    [
        S.document_workflow_fig4,
        lambda: S.shipping_workflow_fig6("lambda-eu-central-1"),
        S.native_prefetch_workflow_fig8,
    ],
)
def test_sigma0_chain_matches_numpy_exactly(make_steps, prefetch):
    sim = S.WorkflowSimulator(_zero_platforms(), seed=0)
    spec = S.ExperimentSpec(_zero_sigma(make_steps()), n_requests=50,
                            prefetch=prefetch, seeds=(0,))
    a, b = _both(sim, spec)
    np.testing.assert_allclose(b, a, atol=ATOL, rtol=0)


@pytest.mark.parametrize("prefetch", [True, False])
def test_sigma0_dag_matches_numpy_exactly(prefetch):
    raw, edges = document_dag_fig4()
    sim = S.WorkflowSimulator(_zero_platforms(), seed=0)
    spec = S.ExperimentSpec(_zero_sigma(raw), edges=edges, n_requests=40,
                            prefetch=prefetch, seeds=(0,))
    a, b = _both(sim, spec)
    np.testing.assert_allclose(b, a, atol=ATOL, rtol=0)


def test_sigma0_mixed_prefetch_flags_dag():
    """A node with prefetch=False inside a prefetch-on experiment: poked
    reachability must flow around it identically on both backends."""
    steps = [
        S.SimStep("a", "tinyfaas-edge", compute=S.Dist(0.2, 0.0)),
        S.SimStep("b", "gcf", compute=S.Dist(0.3, 0.0), fetch=S.Dist(0.4, 0.0)),
        S.SimStep(
            "c",
            "lambda-us-east-1",
            compute=S.Dist(0.5, 0.0),
            fetch=S.Dist(0.6, 0.0),
            prefetch=False,
        ),
        S.SimStep(
            "d",
            "lambda-eu-central-1",
            compute=S.Dist(0.25, 0.0),
            fetch=S.Dist(0.9, 0.0),
        ),
    ]
    edges = [("a", "b"), ("a", "c"), ("b", "d"), ("c", "d")]
    sim = S.WorkflowSimulator(_zero_platforms(), seed=0)
    spec = S.ExperimentSpec(steps, edges=edges, n_requests=60, seeds=(0,))
    a, b = _both(sim, spec)
    np.testing.assert_allclose(b, a, atol=ATOL, rtol=0)


def test_sigma0_cold_regime_matches_numpy_exactly():
    """Arrival gaps straddle keep_warm: the sequential cold recurrence is
    live, exercising the parallel-scan mask end to end."""
    sim = S.WorkflowSimulator(_zero_platforms(keep_warm=2.5), seed=0)
    spec = S.ExperimentSpec(
        _zero_sigma(S.document_workflow_fig4()),
        n_requests=80,
        interarrival_s=3.0,
        seeds=(0,),
    )
    a, b = _both(sim, spec)
    np.testing.assert_allclose(b, a, atol=ATOL, rtol=0)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pallas_sweep_matches_parallel_scan_in_a_cold_regime(monkeypatch, dtype):
    """The whole sweep with the Pallas cold scan (interpret mode here), its
    (seed, placement) rows folded into the kernel's lanes, against the
    parallel-scan sweep: 2 seeds x 4 placements x 600 requests with the
    arrival gap (3.0 s) straddling keep_warm (2.5 s), so the mask decides
    many totals. A row mixed up in the fold moves a total by its cold
    start; the totals must be equal bit for bit."""
    from benchmarks.jaxsim_bench import candidate_placements
    from repro.core import jaxsim

    placements = candidate_placements(4)
    spec = S.ExperimentSpec(
        placements[0], n_requests=600, interarrival_s=3.0, seeds=(3, 4)
    )
    plats = [replace(p, keep_warm_s=2.5) for p in S.paper_platforms()]

    def sweep(platforms, pallas):
        monkeypatch.setattr(jaxsim, "use_pallas", lambda: pallas)
        sim = S.WorkflowSimulator(platforms, seed=0)
        return sim.simulate_placements(spec, placements, dtype=dtype)

    kernel, parallel = sweep(plats, True), sweep(plats, False)
    assert kernel.shape == (2, 4, 600) and kernel.dtype == dtype
    assert kernel.tobytes() == parallel.tobytes()
    warm = sweep([replace(p, keep_warm_s=math.inf) for p in plats], False)
    assert 0.2 < np.mean(kernel != warm) < 1.0  # cold starts in most rows


def test_sigma0_drift_matches_numpy_exactly():
    drift = S.DriftSchedule(
        [
            S.DriftEvent(
                at_request=10,
                platform="gcf",
                compute_scale=3.0,
                transfer_scale=2.0,
                fetch_scale=1.5,
            ),
            S.DriftEvent(
                at_request=25, platform="lambda-us-east-1", transfer_scale=4.0
            ),
        ]
    )
    sim = S.WorkflowSimulator(_zero_platforms(), seed=0, drift=drift)
    spec = S.ExperimentSpec(_zero_sigma(S.document_workflow_fig4()),
                            n_requests=40, seeds=(0,))
    a, b = _both(sim, spec)
    np.testing.assert_allclose(b, a, atol=ATOL, rtol=0)


# ---------------------------------------------------------------------------
# a wide DAG: the 1000Genome workflow of one chromosome (64 steps, 76 edges,
# a 48-way join, 3 topological levels), swept level by level
# ---------------------------------------------------------------------------
GENOME_SEEDS, GENOME_N = (3, 2**40 + 7), 600


def _ladder_dag():
    """Eight steps in four levels of two, one run of one width and one
    largest in-degree that the sweep scans: the sources step with it, the
    join pads one node of each later level, ``s2`` is a sink mid-run, and
    the listing splits levels 1 ({a1, b1}) and 2 ({s2, a2})."""
    names = ["a0", "b0", "a1", "s2", "b1", "a2", "a3", "b3"]
    steps = [
        S.SimStep(name, "lambda-eu-central-1", compute=S.Dist(1.0 + 0.1 * i, 0.2),
                  fetch=S.Dist(0.3, 0.3))
        for i, name in enumerate(names)
    ]
    edges = [("a0", "a1"), ("b0", "a1"), ("b0", "b1"), ("a1", "s2"), ("a1", "a2"),
             ("b1", "a2"), ("a2", "a3"), ("b1", "a3"), ("a2", "b3")]
    return steps, edges


def _moved(name):
    return name[0] == "a" or (name.startswith("individuals_")
                              and name != "individuals_merge")


def _genome_sweep(zero=True, keep_warm=None, interarrival=1.0, dag=genome_dag_1chr,
                  **sim_kw):
    """The simulator, spec and four candidate placements of the benchmark's
    replay mix: candidate i moves the 48 ``individuals`` (of the ladder,
    the ``a`` steps) to platform i."""
    steps, edges = dag()
    plats = _zero_platforms(keep_warm) if zero else [
        replace(p, **({} if keep_warm is None else {"keep_warm_s": keep_warm}))
        for p in S.paper_platforms()
    ]
    if zero:
        steps = _zero_sigma(steps)
    names = [p.name for p in plats]
    cands = [
        [replace(s, platform=names[i]) if _moved(s.name) else s for s in steps]
        for i in range(4)
    ]
    sim = S.WorkflowSimulator(plats, seed=0, **sim_kw)
    spec = S.ExperimentSpec(steps, edges=edges, n_requests=GENOME_N,
                            interarrival_s=interarrival, seeds=GENOME_SEEDS)
    return sim, spec, cands


GENOME_FAULTS = FaultSchedule(
    [
        FaultEvent("lambda-eu-central-1", p_error=0.3, from_request=5, to_request=300),
        OutageEvent(from_request=40, to_request=60, platform="gcf"),
    ],
    seed=3,
)
GENOME_DRIFT = S.DriftSchedule(
    [
        S.DriftEvent(at_request=100, platform="gcf", compute_scale=3.0,
                     transfer_scale=2.0, fetch_scale=1.5),
        S.DriftEvent(at_request=250, platform="lambda-eu-central-1",
                     compute_scale=1.7, transfer_scale=1.3),
    ]
)
# a request about 16 s after the last finds a step idle for 11 to 12 s
# (sources) or less: across keep_warm's 11.5 s, so the cold recurrence is live
GENOME_COLD = {"keep_warm": 11.5, "interarrival": 16.0}
GENOME_EXTRAS = {
    "plain": {},
    "stream": {"stream": S.StreamConfig(chunks=4)},
    "faults": {"faults": GENOME_FAULTS,
               "retry": RetryPolicy(max_attempts=3, backoff_base_s=0.05)},
    "drift": {"drift": GENOME_DRIFT},
    "cold": GENOME_COLD,
}


DAGS = {"genome": genome_dag_1chr, "ladder": _ladder_dag}


@pytest.mark.parametrize("dag", DAGS)
@pytest.mark.parametrize("extra", GENOME_EXTRAS)
def test_sigma0_genome_dag_matches_numpy_bit_for_bit(extra, dag):
    """At sigma 0 every placement's float64 totals over the 64-step,
    76-edge graph (and the ladder, whose levels step as one scan) are the
    numpy backend's bit for bit: the join's max over in-edges is exact,
    and each (node, request) adds in the same order, whatever level the
    node runs in. With faults the dead requests are inf on both
    backends."""
    kw = dict(GENOME_EXTRAS[extra])
    sweep_kw = {k: kw.pop(k) for k in ("keep_warm", "interarrival") if k in kw}
    sim, spec, cands = _genome_sweep(dag=DAGS[dag], **sweep_kw, **kw)
    jx = sim.simulate_placements(spec, cands)
    npy = np.stack(
        [sim.simulate(replace(spec, steps=tuple(p)), backend="numpy") for p in cands],
        axis=1,
    )
    assert jx.shape == (2, 4, GENOME_N) and jx.dtype == np.float64
    assert jx.tobytes() == npy.tobytes()
    if extra == "faults":
        assert 0 < np.isinf(jx).mean() < 0.5


@pytest.mark.parametrize("dag", DAGS)
def test_sigma0_genome_dag_traces_match_numpy_node_for_node(dag):
    """The sampled per-node values come back in topo order from the three
    levels (the ladder's scanned run): each sampled request's node spans
    (times, cold, fetch, compute, payload arrivals) are the numpy
    backend's."""
    from repro.obs import Tracer

    sim, spec, cands = _genome_sweep(dag=DAGS[dag], **GENOME_COLD)
    traces = {}
    for backend in ("jax", "numpy"):
        tracer = Tracer(sample=5)
        sim.simulate(replace(spec, seeds=GENOME_SEEDS[:1], tracer=tracer),
                     backend=backend)
        traces[backend] = {t.root.attrs["request_k"]: t for t in tracer.traces()}
    assert sorted(traces["jax"]) == sorted(traces["numpy"]) and len(traces["jax"]) == 5
    keys = ("cold_s", "fetch_s", "compute_s", "compute_t0", "payload_t")
    for k, jt in traces["jax"].items():
        jn, nn = jt.node_spans(), traces["numpy"][k].node_spans()
        assert list(jn) == list(nn) and len(jn) == len(cands[0])
        for name, a in jn.items():
            b = nn[name]
            assert (a.t_start, a.t_end) == (b.t_start, b.t_end), (k, name)
            assert {x: a.attrs[x] for x in keys} == {x: b.attrs[x] for x in keys}


@pytest.mark.parametrize("dag", DAGS)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pallas_genome_sweep_matches_parallel_scan_in_a_cold_regime(
    monkeypatch, dtype, dag
):
    """The 1000Genome sweep (and the ladder's scanned run) with the Pallas
    cold scan (interpret mode), one kernel call per level over every
    (seed, placement, node) row of the level, against the parallel-scan
    sweep, with spread, in the cold regime above: equal bit for bit. A
    node's row mixed up with another's moves a total by a cold start."""
    from repro.core import jaxsim

    sim, spec, cands = _genome_sweep(zero=False, dag=DAGS[dag], **GENOME_COLD)

    def sweep(pallas, s=sim):
        monkeypatch.setattr(jaxsim, "use_pallas", lambda: pallas)
        return s.simulate_placements(spec, cands, dtype=dtype)

    kernel, parallel = sweep(True), sweep(False)
    assert kernel.shape == (2, 4, GENOME_N) and kernel.dtype == dtype
    assert kernel.tobytes() == parallel.tobytes()
    warm, _, _ = _genome_sweep(zero=False, keep_warm=math.inf, dag=DAGS[dag],
                               interarrival=GENOME_COLD["interarrival"])
    assert 0.2 < np.mean(kernel != sweep(False, warm)) < 1.0


def test_runs_group_levels_of_one_shape():
    """A chain is one run however deep; the 1000Genome graph's three
    levels differ in width and stay three; the ladder is one run."""
    from repro.core import jaxsim
    from repro.core.simulator import _spec_graph

    def runs(steps, edges):
        order, smap, preds, succs = _spec_graph(steps, edges)
        sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
        _, _, graph, _ = jaxsim._build(
            sim, order, [smap], preds, succs, np.zeros(1), None, np.float64
        )
        return [[len(lv.nodes) for lv in run] for run in jaxsim._runs(graph.levels)]

    chain = [S.SimStep(f"s{i}", "gcf", compute=S.Dist(1.0, 0.1)) for i in range(64)]
    assert runs(chain, None) == [[1] * 64]
    assert runs(*genome_dag_1chr()) == [[49], [1], [14]]
    assert runs(*_ladder_dag()) == [[2, 2, 2, 2]]


def test_graph_levels_are_longest_paths_from_a_source():
    from repro.core import jaxsim
    from repro.core.simulator import _spec_graph

    steps, edges = genome_dag_1chr()
    order, _, preds, _ = _spec_graph(steps, edges)
    levels = jaxsim.graph_levels(order, preds)
    assert [len(lv) for lv in levels] == [49, 1, 14]
    assert levels[1] == (49,) and order[49] == "individuals_merge"
    fig4 = S.document_workflow_fig4()
    order, _, preds, _ = _spec_graph(fig4, None)
    assert jaxsim.graph_levels(order, preds) == [(0,), (1,), (2,), (3,)]
    raw, dag = document_dag_fig4()
    order, _, preds, _ = _spec_graph(raw, dag)
    assert jaxsim.graph_levels(order, preds) == [(0,), (1, 2), (3,)]


# ---------------------------------------------------------------------------
# frozen reference: the jax draw contract
# ---------------------------------------------------------------------------
# Per seed: PRNGKey(seed) split into (cold, fetch, compute) streams, one
# (n_nodes, n_requests) standard-normal block each, node-major in topo
# order, drawn by jax's default threefry. Regenerating these numbers
# requires an intentional, documented change to that contract (or to the
# recurrence itself). They were last regenerated for jax 0.9.0, whose
# threefry is partitionable by default (jax >= 0.5 draws other bits for
# the same key than the 0.4 series did).
FROZEN_JAX_FIG4 = [
    5.561552602649,
    2.349538937807,
    2.299148470759,
    2.460183939934,
]


def test_frozen_reference_jax_backend():
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=3)
    spec = S.ExperimentSpec(S.document_workflow_fig4(), n_requests=4, seeds=(3,))
    out = sim.simulate(spec, backend="jax")
    assert out[0].tolist() == pytest.approx(FROZEN_JAX_FIG4, abs=1e-9)


def test_frozen_reference_unchanged_by_single_chunk_stream():
    """chunks=1 keeps the compiled program and draws identical: the jax
    backend reproduces the frozen reference bit-for-bit with a degenerate
    StreamConfig attached (via the spec override)."""
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=3)
    spec = S.ExperimentSpec(
        S.document_workflow_fig4(),
        n_requests=4,
        seeds=(3,),
        stream=S.StreamConfig(chunks=1),
    )
    out = sim.simulate(spec, backend="jax")
    base = S.WorkflowSimulator(S.paper_platforms(), seed=3).simulate(
        S.ExperimentSpec(S.document_workflow_fig4(), n_requests=4, seeds=(3,)),
        backend="jax",
    )
    assert np.array_equal(np.asarray(out), np.asarray(base))
    assert out[0].tolist() == pytest.approx(FROZEN_JAX_FIG4, abs=1e-9)


# ---------------------------------------------------------------------------
# statistical equivalence with spread on
# ---------------------------------------------------------------------------
def test_median_and_p99_agree_within_1pct():
    """Different rngs, same distributions: pooled (3 pinned seeds x 4000
    requests) medians and p99s within 1% — deterministic, not flaky."""
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    spec = S.ExperimentSpec(S.document_workflow_fig4(), n_requests=4000,
                            seeds=(0, 1, 2))
    a, b = _both(sim, spec)
    assert np.median(b) == pytest.approx(np.median(a), rel=0.01)
    assert np.percentile(b, 99) == pytest.approx(np.percentile(a, 99), rel=0.01)


# ---------------------------------------------------------------------------
# the placement axis: CRN across a batched candidate set
# ---------------------------------------------------------------------------
def test_batched_placements_share_draws_crn():
    """Placements in one batched sweep share the per-seed draws (CRN):
    the same placement listed twice yields bit-identical rows, so row
    differences are placement effects, not sampling noise. Against a
    SEPARATE solo sweep the rows agree to float32 factor noise — the
    sigma table is pooled across the batch, so the two calls compile
    different programs and XLA's f32 exp fusion may differ at ~1e-7."""
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    fig4 = S.document_workflow_fig4()
    placements = [fig4, _zero_sigma(fig4), fig4]
    spec = S.ExperimentSpec(fig4, n_requests=100, seeds=(5, 6))
    both = sim.simulate_placements(spec, placements)
    assert both.shape == (2, 3, 100)
    assert np.array_equal(both[:, 0, :], both[:, 2, :])  # CRN, bit-exact
    assert not np.array_equal(both[:, 0, :], both[:, 1, :])
    for j, steps in enumerate(placements[:2]):
        solo = sim.simulate_placements(replace(spec, steps=tuple(steps)), [steps])
        np.testing.assert_allclose(both[:, j, :], solo[:, 0, :], rtol=1e-6)


def test_batched_sweep_is_deterministic():
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    fig4 = S.document_workflow_fig4()
    spec = S.ExperimentSpec(fig4, n_requests=64, seeds=(1, 2))
    a = sim.simulate_placements(spec, [fig4, _zero_sigma(fig4)])
    b = sim.simulate_placements(spec, [fig4, _zero_sigma(fig4)])
    assert np.array_equal(a, b)


def test_a_simulator_keeps_the_inputs_it_sweeps_again_on_the_device(monkeypatch):
    """A simulator keeps the inputs it sweeps again on the device: a first
    sweep hands the program host arrays, the same workflow under new seeds
    hands it only the seeds' keys from the host, a moved step hands it what
    it changed, and the totals are a fresh simulator's bit for bit every
    time."""
    import jax

    from repro.core import jaxsim

    host = []
    real = jaxsim._sweep

    def sweep(*args, **kwargs):
        host.append(sum(isinstance(a, np.ndarray | np.generic)
                        for a in jax.tree.leaves(args)))
        return real(*args, **kwargs)

    monkeypatch.setattr(jaxsim, "_sweep", sweep)
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    fig4 = S.document_workflow_fig4()
    moved = fig4[:1] + [replace(fig4[1], platform="lambda-eu-central-1")] + fig4[2:]
    spec = S.ExperimentSpec(fig4, n_requests=64, seeds=(1, 2))
    sweeps = [
        (spec, [fig4, _zero_sigma(fig4)]),
        (replace(spec, seeds=(3, 4)), [fig4, _zero_sigma(fig4)]),
        (replace(spec, seeds=(5, 6)), [fig4, _zero_sigma(fig4)]),
        (replace(spec, seeds=(5, 6)), [fig4, moved]),
    ]
    for sp, cands in sweeps:
        got = sim.simulate_placements(sp, cands, dtype=np.float32)
        fresh = S.WorkflowSimulator(S.paper_platforms(), seed=0)
        want = fresh.simulate_placements(sp, cands, dtype=np.float32)
        assert np.array_equal(got, want)
    first, again, third, moved_host = host[::2]
    assert again == third == 1  # the keys
    assert first > moved_host > 1


def test_simulate_placements_default_seed_and_f32():
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=11)
    steps = S.document_workflow_fig4()
    spec = S.ExperimentSpec(steps, n_requests=64)
    out = sim.simulate_placements(spec, [steps])
    assert out.shape == (1, 1, 64)  # seeds=None -> the construction seed
    named = sim.simulate_placements(replace(spec, seeds=(11,)), [steps])
    assert np.array_equal(out, named)
    lo = sim.simulate_placements(spec, [steps], dtype=np.float32)
    assert np.median(lo) == pytest.approx(np.median(out), rel=1e-4)


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------
def test_jax_rejects_timing_controller():
    from repro.core.timing import PokeTimingController

    sim = S.WorkflowSimulator(
        S.paper_platforms(), seed=0, timing=PokeTimingController()
    )
    with pytest.raises(ValueError, match="timing"):
        sim.simulate(
            S.ExperimentSpec(S.document_workflow_fig4(), n_requests=4), backend="jax"
        )


def test_jax_rejects_telemetry():
    from repro.adapt import TelemetryHub

    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0, telemetry=TelemetryHub())
    with pytest.raises(ValueError, match="telemetry"):
        sim.simulate(
            S.ExperimentSpec(S.document_workflow_fig4(), n_requests=4), backend="jax"
        )


def test_jax_rejects_duplicate_name_platform_nodes():
    steps = [
        S.SimStep("f", "gcf", compute=S.Dist(0.1)),
        S.SimStep("f", "gcf", compute=S.Dist(0.1)),
    ]
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    with pytest.raises(ValueError, match="unique"):
        sim.simulate(S.ExperimentSpec(steps, n_requests=4), backend="jax")


def test_jax_zero_requests_and_infinite_keep_warm():
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    out = sim.simulate(
        S.ExperimentSpec(S.document_workflow_fig4(), n_requests=0), backend="jax"
    )
    assert out.shape == (0,)
    plats = [
        S.SimPlatform(
            "p",
            "r",
            native_prefetch=True,
            cold_start=S.Dist(0.5, 0.0),
            keep_warm_s=math.inf,
        )
    ]
    steps = [S.SimStep("a", "p", compute=S.Dist(0.2, 0.0))]
    sim = S.WorkflowSimulator(plats, seed=0)
    spec = S.ExperimentSpec(steps, n_requests=8, seeds=(0,))
    a, b = _both(sim, spec)
    np.testing.assert_allclose(b, a, atol=ATOL, rtol=0)

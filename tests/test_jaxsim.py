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
from repro.dag import document_dag_fig4

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

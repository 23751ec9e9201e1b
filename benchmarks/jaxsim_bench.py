"""jax backend vs numpy backend on the scorer-shaped sweep (jaxsim bench).

The workload that motivates the jax backend is not one experiment but the
candidate-set sweep the ``PlacementScorer`` runs inside the recomposition
controller: (seeds x placements x requests) totals for a whole candidate
placement set under common random numbers. The numpy backend pays one
vectorized experiment per (seed, placement) cell; the jax backend compiles
the entire sweep into ONE jitted program (``simulate_placements``) and
amortizes sampling across it — pre-tabulated lognormal factors per
distinct sigma, static poke depths, an early-out parallel cold scan.

  - SPEED: the full sweep (8 seeds x 32 placements x 512 requests)
    through ``simulate_placements`` (f32) must be >= 5x faster than the
    numpy backend on the same sweep, compile time excluded (measured:
    ~8x on CI-class CPUs). ``--quick`` shrinks the sweep and only gates
    jax >= numpy (tiny sweeps under-fill the compiled program).
  - AGREEMENT: per-placement medians and the pooled p99 of the two
    backends land within 1% (different rngs, same distributions; pinned
    seeds make the gap deterministic).

Output: CSV-ish ``name,value`` rows; ``run.py`` writes them to
``experiments/bench/BENCH_jaxsim.json`` so the speedup is tracked across
commits.
"""

from __future__ import annotations

import time
from dataclasses import replace

import numpy as np

from repro.core import simulator as S


def candidate_placements(count: int) -> list:
    """``count`` distinct placements of the document workflow: rotate the
    platform of one middle step through the paper's platform set."""
    base = S.document_workflow_fig4()
    plats = [p.name for p in S.paper_platforms()]
    out = []
    for i in range(count):
        steps = list(base)
        j = 1 + i % (len(steps) - 2)
        steps[j] = replace(steps[j], platform=plats[i % len(plats)])
        out.append(steps)
    return out


def main(
    n: int = 512, n_placements: int = 32, seeds=tuple(range(8)), quick: bool = False
) -> dict:
    if quick:
        n, n_placements, seeds = 128, 8, (0, 1, 2, 3)
    placements = candidate_placements(n_placements)
    spec = S.ExperimentSpec(placements[0], n_requests=n, seeds=tuple(seeds))
    rows = {
        "n_requests": float(n),
        "n_placements": float(n_placements),
        "n_seeds": float(len(seeds)),
    }

    # -- numpy backend: one vectorized experiment per (seed, placement) --------
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    t0 = time.perf_counter()
    np_tot = np.stack(
        [
            sim.simulate(replace(spec, steps=tuple(steps)), backend="numpy")
            for steps in placements
        ],
        axis=1,
    )  # (S, P, n)
    rows["numpy_sweep_s"] = time.perf_counter() - t0

    # -- jax backend: the whole sweep is one jitted call ------------------------
    t0 = time.perf_counter()
    jx_tot = sim.simulate_placements(spec, placements, dtype=np.float32)
    rows["jax_first_call_s"] = time.perf_counter() - t0  # includes compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jx_tot = sim.simulate_placements(spec, placements, dtype=np.float32)
        best = min(best, time.perf_counter() - t0)
    rows["jax_sweep_s"] = best
    rows["speedup_x"] = rows["numpy_sweep_s"] / rows["jax_sweep_s"]

    # -- agreement (pinned seeds -> deterministic, not flaky) -------------------
    med_np = np.median(np_tot, axis=(0, 2))  # per-placement medians
    med_jx = np.median(jx_tot, axis=(0, 2))
    rows["median_gap_pct"] = float(np.abs(med_jx - med_np).max() / med_np.min()) * 100
    p99_np, p99_jx = np.percentile(np_tot, 99), np.percentile(jx_tot, 99)
    rows["p99_gap_pct"] = abs(p99_jx - p99_np) / p99_np * 100

    print("name,value")
    for name, value in rows.items():
        print(f"{name},{value:.6f}")
    cells = len(seeds) * n_placements * n
    print(f"derived,requests_per_second_jax,{cells / rows['jax_sweep_s']:.0f}")

    assert rows["speedup_x"] >= (1.0 if quick else 5.0), rows
    # quick pools ~4k samples, too few to pin the 99th percentile tighter;
    # the 1% gates on the full sweep are the real agreement ratchet
    assert rows["median_gap_pct"] <= (3.0 if quick else 1.0), rows
    assert rows["p99_gap_pct"] <= (6.0 if quick else 1.0), rows
    return rows


if __name__ == "__main__":
    main()

"""A workflow DAG through the placement sweep's whole run, on the CPU.

The fixture is no cell of the benchmark: ``fig4-paper``'s platforms and
object store, a source that fans out to six branches, their fan-in, a
second source, and two sinks that each read the fan-in and the second
source. It runs through ``harness.run`` as the cells do, with the shared
plain reference (``bench/sweep_reference.py``) and limits set here: a
sound run is correct, the bfloat16 control reads past three times each
limit, each planted fault turns ``correct`` false, and at float64 the
program's totals are the reference's.
"""

import os
import sys
import time
import types

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import faults, harness, traffic  # noqa: E402
from bench import sweep_reference as R  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**40 + 77
SPEC = harness.load_spec()
FIG4 = harness.config("fig4-paper")
BRANCHES = [f"branch_{i}" for i in range(6)]


def step(name, platform, compute, fetch):
    return {"name": name, "platform": platform, "compute": [compute, 0.12],
            "fetch": [fetch, 0.12]}


CONFIG = dict(
    FIG4,
    workflow=[step("split", "tinyfaas-edge", 0.22, 0.0)]
    + [step(b, ("gcf", "lambda-us-east-1")[i % 2], 0.30, 0.32)
       for i, b in enumerate(BRANCHES)]
    + [step("merge", "lambda-us-east-1", 0.45, 1.45),
       step("sift", "gcf", 0.25, 0.5),
       step("sink_a", "lambda-eu-central-1", 0.20, 0.85),
       step("sink_b", "lambda-us-east-1", 0.20, 0.85)],
    edges=[["split", b] for b in BRANCHES] + [[b, "merge"] for b in BRANCHES]
    + [[a, b] for a in ("merge", "sift") for b in ("sink_a", "sink_b")],
)
GROUPS = {"kind": "rotate_groups", "groups": [BRANCHES, ["merge"], ["sift", "sink_a"]]}
MIXES = {
    "replay": {"loop": "closed", "call": "simulate_placements", "n_requests": 2048,
               "sweep_seeds": 2, "placements": dict(GROUPS, count=4),
               "dtype": "float32"},
    "decide": {"loop": "closed", "call": "scorer", "n_requests": 64, "sweep_seeds": 2,
               "placements": dict(GROUPS, count=6), "dtype": "float32",
               "scorer": {"quantile": 0.95, "sigma": 0.12}},
}
# the limits of the fig4 cells of the same call
LIMITS = {"replay": {"end_time_ulp_gap": 80},
          "decide": {"end_time_ulp_gap": 150, "quantile_rel_gap": 2e-4}}
CELLS = [f"dag-fixture.{m}" for m in MIXES]
DAG_SPEC = dict(SPEC, workloads=SPEC["workloads"] + [
    {"name": c, "config": "dag-fixture", "traffic": c.split(".")[1], "chips": 1,
     "why": "test"} for c in CELLS])


@pytest.fixture
def dag(monkeypatch):
    """The harness finds the fixture's reference and limits."""
    real = harness.system

    def system(name):
        class Fixture(real(name).System):
            def check(self, result, control=False):
                limits = LIMITS[self.cell.split(".")[1]]
                readings = self.readings(result, control)
                return {k: {"value": v, "limit": limits[k]}
                        for k, v in readings.items()}
        return types.SimpleNamespace(System=Fixture)

    monkeypatch.setattr(harness, "system", system)
    monkeypatch.setattr(harness, "reference", lambda name: R)


def run(name, seed=SEED, control=False):
    cell = harness.Cell(DAG_SPEC, name, config_=CONFIG, mix_=MIXES[name.split(".")[1]])
    jax.clear_caches()  # a planted fault must reach a freshly traced program
    line = harness.run(cell, seed, 1.0, False, time.perf_counter(), CPU,
                       out=lambda *a, **k: None, control=control)
    jax.clear_caches()
    return line


@pytest.mark.parametrize("name", CELLS)
def test_dag_sound_run_is_correct(name, dag):
    line = run(name)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 2 and line["failed"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_dag_control_fails_the_limit(name, dag):
    for seed in (1, 2, SEED):
        line = run(name, seed=seed, control=True)
        assert line["correct"] is False
        numbers = {k: c for k, c in line["checks"].items() if k != "window_compiles"}
        assert set(numbers) == set(LIMITS[name.split(".")[1]])
        for c in numbers.values():
            assert c["value"] > 3 * c["limit"], line["checks"]


@pytest.mark.parametrize("name,fault", [
    ("dag-fixture.replay", "answer"),
    ("dag-fixture.replay", "half_batch"),
    ("dag-fixture.replay", "cold_state_unchanged"),
    ("dag-fixture.replay", "cold_flipped_late"),
    ("dag-fixture.decide", "answer"),
    ("dag-fixture.decide", "half_batch"),
])
def test_dag_fault_turns_correct_false(name, fault, dag):
    undo = faults.plant(fault)
    try:
        line = run(name)
    finally:
        undo()
    assert line["correct"] is False
    assert line["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("sigma,rtol", [(0.0, 1e-9), (0.12, 1e-6)])
def test_dag_program_totals_are_the_references_at_float64(sigma, rtol):
    """With no spread the two agree to 1e-9; with spread the program
    tabulates each lognormal factor exp(sigma * z) in float32 before the
    float64 recurrence (``core/jaxsim.py``), some 6e-8 of a total."""
    wf = [dict(s, compute=[s["compute"][0], sigma], fetch=[s["fetch"][0], sigma])
          for s in CONFIG["workflow"]]
    plats = [dict(p, cold_start=[p["cold_start"][0], sigma])
             for p in CONFIG["platforms"]]
    cfg = dict(CONFIG, workflow=wf, platforms=plats)
    mix = dict(MIXES["replay"], dtype="float64")
    sysm = harness.system("placement_sweep").System(
        "dag-fixture.replay", cfg, mix, SEED, R, lambda *a: None)
    sysm.setup({})
    got, _ = sysm._call(3)
    want = R.sweep_totals(cfg, mix, sysm.cands, traffic.sweep_seeds(SEED, 3, 2))
    assert got.shape == want.shape == (2, 4, 2048) and got.dtype == np.float64
    assert np.max(np.abs(got - want) / want) < rtol
    # the candidates are priced apart: moving a group moves the totals
    assert len({float(np.median(got[0, p])) for p in range(4)}) > 1

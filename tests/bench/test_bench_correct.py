"""The comparison that decides ``correct``, at sizes a test run can hold.

Each cell's whole run (set-up, window, the plain reference) is driven here
on the CPU, past the harness's look for a chip. A sound run is correct;
the control (the reference itself in the precision below the
configuration's) reads past the limit; and each fault the cell can have,
planted in the program underneath the timed path, turns ``correct`` false.
The limits are the cells' own (``bench/limits``).
"""

import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import faults, harness  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**40 + 77
SPEC = harness.load_spec()


def run(cell, seed=SEED, seconds=1.0, control=False):
    jax.clear_caches()  # a planted fault must reach a freshly traced program
    line = harness.run(cell, seed, seconds, False, time.perf_counter(), CPU,
                       out=lambda *a, **k: None, control=control)
    jax.clear_caches()
    return line


# -- the placement sweep ---------------------------------------------------------
def sweep_cell(name):
    m = harness.mix(name.split(".")[1])
    if m["call"] == "scorer":
        fewer = dict(m["placements"], count=8)
        m = dict(m, n_requests=64, sweep_seeds=2, placements=fewer)
    else:
        m = dict(m, n_requests=2048)
    return harness.Cell(SPEC, name, mix_=m)


SWEEPS = ["fig4-paper.decide", "fig4-paper.throughput"]


@pytest.mark.parametrize("name", SWEEPS)
def test_sweep_sound_run_is_correct(name):
    line = run(sweep_cell(name))
    assert line["correct"] is True
    assert line["attempted"] > 2 and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", SWEEPS)
def test_sweep_control_fails_the_limit(name):
    """The reference in bfloat16, in the program's place, on three seeds:
    the harness's own comparison reads every number of the cell past its
    limit."""
    for seed in (1, 2, SEED):
        line = run(sweep_cell(name), seed=seed, control=True)
        assert line["correct"] is False
        numbers = {k: c for k, c in line["checks"].items() if k != "window_compiles"}
        assert len(numbers) == (2 if name.endswith("decide") else 1)
        for c in numbers.values():
            assert c["value"] > 3 * c["limit"], line["checks"]


FAULTS = [
    ("fig4-paper.decide", "answer"),
    ("fig4-paper.decide", "half_batch"),
    ("fig4-paper.throughput", "answer"),
    ("fig4-paper.throughput", "half_batch"),
    # the scorer's platforms never go cold, so only the throughput cell
    # carries the cold scan's state
    ("fig4-paper.throughput", "cold_state_unchanged"),
    ("fig4-paper.throughput", "cold_flipped_late"),
]


@pytest.mark.parametrize("name,fault", FAULTS)
def test_sweep_fault_turns_correct_false(name, fault):
    undo = faults.plant(fault)
    try:
        line = run(sweep_cell(name))
    finally:
        undo()
    assert line["correct"] is False
    assert line["checks"]["window_compiles"]["value"] == 0


def test_compile_inside_the_window_turns_correct_false(monkeypatch):
    real = harness.system

    def system(name):
        class Recompiling(real(name).System):
            def window(self, seconds, prof):
                jax.clear_caches()  # the window's programs compile again
                return super().window(seconds, prof)
        return types.SimpleNamespace(System=Recompiling)

    monkeypatch.setattr(harness, "system", system)
    line = run(sweep_cell("fig4-paper.decide"))
    assert line["correct"] is False
    assert line["checks"]["window_compiles"]["value"] > 0
    rest = [c for k, c in line["checks"].items() if k != "window_compiles"]
    assert all(c["value"] <= c["limit"] for c in rest)


# -- the served workflow ---------------------------------------------------------
TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=256, vocab_size=4096)


# the served cells are not in BENCHMARK.json yet (PERF.md, open questions):
# the test runs them under a spec that adds them
SERVED_SPEC = dict(
    SPEC,
    workloads=SPEC["workloads"] + [
        {"name": f"qwen3-1.7b-twopod.{mix}", "config": "qwen3-1.7b-twopod",
         "traffic": mix, "chips": 1, "why": "test"}
        for mix in ("doc", "short")
    ],
    end_to_end=SPEC["end_to_end"] + [
        {"name": f"workflow_{q}_ms", "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock",
         "workloads": ["qwen3-1.7b-twopod.doc", "qwen3-1.7b-twopod.short"]}
        for q in ("p50", "p90")
    ],
)


def served_cell(name, rate=3.0):
    cfg = harness.config("qwen3-1.7b-twopod")
    cfg = dict(cfg, **TINY)
    cfg["arch"] = dict(
        cfg["arch"], num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
        head_dim=32, d_ff=256, vocab_size=TINY["vocab_size"],
    )
    m = dict(harness.mix(name.split(".")[-1]), rate_per_s=rate,
             prompt_tokens={"values": [16, 32], "probs": [0.5, 0.5]},
             new_tokens={"values": [8, 16], "probs": [0.5, 0.5]})
    return harness.Cell(SERVED_SPEC, name, config_=cfg, mix_=m)


SERVED = ["qwen3-1.7b-twopod.doc", "qwen3-1.7b-twopod.short"]


@pytest.mark.parametrize("name", SERVED)
def test_served_sound_run_is_correct(name):
    line = run(served_cell(name), seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 6
    assert set(line["metrics"]) == {"setup_s", "workflow_p50_ms", "workflow_p90_ms"}


def test_served_control_fails_the_limit():
    """The float8 evaluation of the reference, at each position of served
    prompts and tokens, on three seeds."""
    from bench.systems import served_workflow as SW
    from repro.configs.base import ArchConfig

    cell = served_cell("qwen3-1.7b-twopod.doc")
    cfg = cell.config
    ref = harness.reference(cell.config_name)
    limits = harness.load_json("limits", "qwen3-1.7b-twopod.doc.json")
    limit = limits["served_logit_err_sd"]
    arch = ArchConfig(**dict(cfg["arch"], block_pattern=("global",)))
    worst = []
    for seed in (1, 2, SEED):
        params = SW.init_weights(arch, seed)
        gen = np.random.default_rng(seed)
        reqs = [(gen.integers(0, TINY["vocab_size"], 32, dtype=np.int32),
                 gen.integers(0, TINY["vocab_size"], 8).tolist(), None)
                for _ in range(8)]
        worst.append(max(ref.control_logit_err(cfg, params, reqs, (40, 8))))
    assert min(worst) > limit, worst


def _token_altered(real):
    def decode_step(cfg, params, token, caches, cur):
        logits, caches = real(cfg, params, token, caches, cur)
        return jnp.roll(logits, 1, axis=-1), caches
    return decode_step


def _state_unchanged(real):
    def decode_step(cfg, params, token, caches, cur):
        logits, _ = real(cfg, params, token, caches, cur)
        return logits, caches
    return decode_step


@pytest.mark.parametrize("fault", ["token", "state"])
def test_served_fault_turns_correct_false(fault, monkeypatch):
    from repro.models import model as M

    wrap = _token_altered if fault == "token" else _state_unchanged
    monkeypatch.setattr(M, "decode_step", wrap(M.decode_step))
    line = run(served_cell("qwen3-1.7b-twopod.doc"), seconds=2.0)
    assert line["correct"] is False, line["checks"]

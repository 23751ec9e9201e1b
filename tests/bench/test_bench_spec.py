"""BENCHMARK.json against the harness: every name resolves to its files,
names and units keep to their alphabet, each per-layer metric moves an
end-to-end metric its cells report, and a run without a TPU exits nonzero
with no result."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def bench_file(*parts):
    return os.path.join(ROOT, "bench", *parts)


def test_top_level_keys_and_command():
    assert set(SPEC) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
        "per_layer",
    }
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench", "tests/bench"]
    for word in SPEC["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)


def test_cells_in_the_issue_order_on_one_chip():
    # the served cells (qwen3-1.7b-twopod.doc, .short) wait in PERF.md's
    # open questions: their tails spread past any bound the check allows
    assert CELLS == ["fig4-paper.decide", "fig4-paper.throughput"]
    assert all(w["chips"] == 1 for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    cfg_names = {c["name"] for c in SPEC["configs"]}
    assert w["config"] in cfg_names
    assert os.path.isfile(bench_file("traffic", f"{w['traffic']}.json"))
    assert os.path.isfile(bench_file("limits", f"{cell}.json"))
    with open(bench_file("configs", f"{w['config']}.json")) as f:
        cfg = json.load(f)
    assert os.path.isfile(bench_file("systems", f"{cfg['system']}.py"))
    assert os.path.isfile(bench_file("configs", f"{w['config']}.reference.py"))
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_and_reduced(cfg):
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    with open(os.path.join(ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    widths = re.compile(r"(_dim|_rank|_size|heads|width|experts_per_tok)$")
    assert not [k for k in cfg["reduced"] if widths.search(k)]
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


def test_qwen3_program_arch_keeps_the_published_widths():
    with open(bench_file("configs", "qwen3-1.7b-twopod.json")) as f:
        c = json.load(f)
    a = c["arch"]
    assert (a["num_layers"], a["d_model"], a["num_heads"], a["num_kv_heads"]) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"],
    )
    assert (a["head_dim"], a["d_ff"], a["vocab_size"]) == (
        c["head_dim"], c["intermediate_size"], c["vocab_size"],
    )
    assert a["tie_embeddings"] == c["tie_word_embeddings"]
    assert a["rope_theta"] == c["rope_theta"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_sources(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        keys = {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(m) <= keys
        sources = ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["source"] in sources
        assert hasattr(harness.reader(m["name"]), "read")
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_what_its_cells_report(m):
    e2e = {e["name"]: e for e in SPEC["end_to_end"]}
    assert m["moves"] in e2e
    moved_in = e2e[m["moves"]].get("workloads", CELLS)
    assert m["workloads"] and set(m["workloads"]) <= set(moved_in)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_e2e_and_a_layer(cell):
    e2e = [e["name"] for e in SPEC["end_to_end"] if cell in e.get("workloads", CELLS)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in m["workloads"] for m in SPEC["per_layer"])


def test_end_to_end_metrics_are_the_issues():
    assert {e["name"] for e in SPEC["end_to_end"]} == {
        "setup_s", "decision_p95_ms", "sim_requests_per_s",
    }
    setup = next(e for e in SPEC["end_to_end"] if e["name"] == "setup_s")
    assert setup["bound"] == 0.25


def test_layer_names_are_one_line_and_shared_per_layer():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig4-paper.decide",
         "--seed", str(2**40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]

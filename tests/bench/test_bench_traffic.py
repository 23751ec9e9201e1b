"""The traffic generator and the FLOP and byte counters."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops, traffic  # noqa: E402
from bench.harness import config, mix  # noqa: E402

BIG_SEED = 2**40 + 12345  # the driver's seeds pass 32 bits


@pytest.mark.parametrize("name", ["doc", "short"])
def test_open_loop_repeats_exactly_per_seed(name):
    m = mix(name)
    a = traffic.open_loop(m, BIG_SEED, 30.0, 151936)
    b = traffic.open_loop(m, BIG_SEED, 30.0, 151936)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    c = traffic.open_loop(m, BIG_SEED + 1, 30.0, 151936)
    assert [r["due"] for r in a] != [r["due"] for r in c]


@pytest.mark.parametrize("name", ["doc", "short"])
def test_open_loop_matches_its_rate_and_stays_in_the_window(name):
    m = mix(name)
    for seed in (0, 7, BIG_SEED):
        sched = traffic.open_loop(m, seed, 40.0, 151936)
        assert len(sched) == round(m["rate_per_s"] * 40.0)
        dues = np.array([r["due"] for r in sched])
        assert dues[0] == 0.0 and np.all(np.diff(dues) > 0) and dues[-1] < 40.0
        gaps = np.diff(np.append(dues, dues[-1] + 0))
        # exponential shape: about 63% of the gaps under the mean gap
        mean_gap = 1.0 / m["rate_per_s"]
        assert 0.5 < np.mean(np.diff(dues) < mean_gap) < 0.75
        assert len(gaps) == len(sched)


@pytest.mark.parametrize("name", ["doc", "short"])
def test_lengths_follow_the_stated_probabilities(name):
    m = mix(name)
    sched = traffic.open_loop(m, BIG_SEED, 50.0, 1000)
    n = len(sched)
    for key, field in (("prompt_tokens", "prompt"), ("new_tokens", "new_tokens")):
        got = [len(r[field]) if field == "prompt" else r[field] for r in sched]
        want = traffic.exact_counts(m[key]["probs"], n)
        counts = [got.count(v) for v in m[key]["values"]]
        assert counts == list(want)
        assert np.allclose(np.array(counts) / n, m[key]["probs"], atol=1.0 / n)
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000 for r in sched)


def test_every_block_offers_the_mix():
    m = mix("doc")
    sched = traffic.open_loop(m, BIG_SEED, 50.0, 100)
    prompts = [len(r["prompt"]) for r in sched]
    for b in range(0, len(prompts), traffic.BLOCK):
        block = prompts[b: b + traffic.BLOCK]
        counts = [block.count(v) for v in m["prompt_tokens"]["values"]]
        assert counts == [4, 3, 2, 1]


def test_seeds_vary_the_order_not_the_amount_of_work():
    m = mix("doc")
    a = traffic.open_loop(m, 1, 50.0, 100)
    b = traffic.open_loop(m, 2, 50.0, 100)
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert sorted(r["new_tokens"] for r in a) == sorted(r["new_tokens"] for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


def test_seed_rules():
    with pytest.raises(ValueError):
        traffic.rng(-1)
    big, small = traffic.rng(2**64 + 5), traffic.rng(5)
    assert big.integers(0, 2**62) != small.integers(0, 2**62)
    s = traffic.sweep_seeds(BIG_SEED, 3, 8)
    assert len(set(s)) == 8 and all(0 <= x < 2**63 for x in s)
    assert s == traffic.sweep_seeds(BIG_SEED, 3, 8)
    assert s != traffic.sweep_seeds(BIG_SEED, 4, 8)


def test_shapes_cover_every_prompt_with_the_longest_answer():
    doc = [(256, 320), (512, 576), (1024, 1088), (2048, 2112)]
    assert traffic.shapes(mix("doc")) == doc
    assert traffic.shapes(mix("short")) == [(64, 68), (128, 132)]


def test_sample_holds_what_it_must():
    picked = traffic.sample(100, 5, BIG_SEED, must=[99])
    assert 99 in picked and len(picked) == 5 and picked == sorted(picked)
    assert traffic.sample(3, 10, 1) == [0, 1, 2]


def test_drift_has_each_steps_calibrated_spread():
    cfg = config("fig4-paper")
    a = traffic.drift_factors(cfg, BIG_SEED, 3, 4)
    assert a.shape == (2, len(cfg["workflow"]), 4) and (a > 0).all()
    assert np.array_equal(a, traffic.drift_factors(cfg, BIG_SEED, 3, 4))
    assert not np.array_equal(a, traffic.drift_factors(cfg, BIG_SEED, 4, 4))
    logs = np.log(np.stack([traffic.drift_factors(cfg, BIG_SEED, c, 4)
                            for c in range(2000)]))
    want = [[s["compute"][1] for s in cfg["workflow"]],
            [s["fetch"][1] for s in cfg["workflow"]]]
    assert np.allclose(logs.std(axis=(0, 3)), want, rtol=0.05)


def test_qwen3_flops_per_token_come_from_the_config():
    hf = config("qwen3-1.7b-twopod")
    layers, head = flops.decoder_matmul_params(hf)
    per_layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
    assert layers == 28 * per_layer and head == 2048 * 151936
    # 3.44 GFLOP of weights per decoded token, 0.12 GFLOP of attention at 512
    attn_per_key = 4 * 28 * 16 * 128
    want = 2 * (layers + head) + attn_per_key
    assert flops.decode_flops(hf, 1) == pytest.approx(want)
    assert flops.decode_flops(hf, 512) / 1e9 == pytest.approx(3.558, abs=0.001)
    t = 512
    want = 2 * layers * t + 2 * head + 4 * 28 * 16 * 128 * t * (t + 1) / 2
    assert flops.prefill_flops(hf, t) == pytest.approx(want)


def test_kernel_work_from_call_shapes():
    f, b = flops.flash_attention_work((1, 16, 256, 128), (1, 8, 256, 128))
    assert f == 4 * 16 * 128 * (256 * 257 // 2)
    assert b == 2 * (2 * 16 * 256 * 128 + 2 * 8 * 256 * 128)
    f, b = flops.flash_attention_work((1, 16, 1, 128), (1, 8, 300, 128), causal=True)
    assert f == 4 * 16 * 128 * 300
    assert flops.cold_scan_work(64, 512) == (0.0, 2 * 4 * 64 * 512)


def test_mixes_are_data_with_their_reasons():
    for name in ("doc", "short", "decide", "throughput"):
        with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
            m = json.load(f)
        assert m["loop"] in ("open", "closed") and m["why"]

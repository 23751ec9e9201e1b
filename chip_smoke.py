#!/usr/bin/env python3
"""Run GeoFF's two device paths once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]     # one chip: phases 1-3
    python chip_smoke.py --four-chips   # four chips: the sharded decode only

Phase 1, device check: stops at once unless JAX's first device is a TPU.

Phase 2, the placement sweep: ``WorkflowSimulator.simulate_placements`` on
the paper's platforms and the Fig-4 document workflow.
  (a) the scorer's shape, 8 seeds x 32 placements x 512 requests in f32,
      and one ``PlacementScorer(backend="jax")`` scoring call;
  (b) the throughput shape, 2^20 requests per (seed, placement);
  (c) a regime where the cold scan decides (interarrival near keep_warm).
  Checks: the compiled sweep holds the Pallas cold-scan kernel
  (``tpu_custom_call``); the sweep agrees with the numpy backend to 1e-9
  at sigma=0 in f64, and within 1% on medians and p99 with spread; the
  kernel's cold mask equals ``cold_scan_parallel`` and ``cold_scan_ref``.

Phase 3, a served workflow at the full width of qwen3-1.7b, weights drawn
from the seed: prefill on one platform and decode on another through
``Deployment.run``, then ``ServingEngine`` with continuous batching
(``examples/federated_serving.py``). Checks: the Pallas prefill holds its
kernel, and its logits match the jnp attention path within a bf16
tolerance.

``--four-chips`` runs only the decode step of phase 3 on a platform bound
to a 4-way model-parallel mesh, parameters and cache placed by the
sharding rules, against the same step on one chip.

Every input is generated from ``--seed`` and the tracked sources. No phase
catches its own failure: a failed check exits nonzero. The last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import sys
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT, os.path.join(ROOT, "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import federated_serving  # noqa: E402
from benchmarks.jaxsim_bench import candidate_placements  # noqa: E402
from repro.adapt import PlacementScorer  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core import Platform, PlacementCosts, bind_sharding, jaxsim  # noqa: E402
from repro.core import simulator as S  # noqa: E402
from repro.core.platform import PlatformWrapper  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.cold_scan import cold_scan_parallel  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import params as prm  # noqa: E402
from repro.serving import pad_cache  # noqa: E402

# sweep shapes: (seeds, placements, requests)
SCORER_SHAPE = (8, 32, 512)  # the controller's decision (jaxsim_bench full)
THROUGHPUT_SHAPE = (2, 4, 2**20)  # peak HBM ~9 GiB with the kernel's padding
COLD_KEEP_WARM_S, COLD_INTERARRIVAL_S = 2.5, 3.0
MASK_SHAPE = (256, 4096)  # (rows, requests) of the direct kernel check

SIGMA0_ATOL = 1e-9  # f64 at sigma=0: reassociated float ops, not new math
SPREAD_REL = 0.01  # medians and p99 with spread: different rngs
# Pallas vs jnp prefill logits, both computed in bf16: max |difference|
# over max |reference logit|, about 13 bf16 ulps of the largest logit
BF16_TOL = 0.05

PROMPT_LEN, NEW_TOKENS = 512, 32


def log(*args):
    print(*args, flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def require_tpu():
    """Phase 1: the device as JAX reports it; exits unless it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def assert_kernel_compiled(name, compiled):
    """A Pallas kernel compiled for the chip shows up as a custom call; in
    interpret mode it would be plain HLO."""
    check("tpu_custom_call" in compiled.as_text(), f"{name}: no tpu_custom_call")
    log(f"  {name}: compiled program holds the Pallas kernel (tpu_custom_call)")


def assert_sweep_kernel(call):
    """The sweep took the Pallas cold scan, and the program it ran, compiled
    again from the recorded arguments, holds the kernel."""
    args, kwargs = call
    check(kwargs["use_pallas"], "the sweep did not take the Pallas cold scan")
    with jax.enable_x64(True):
        assert_kernel_compiled("_sweep", jaxsim._sweep.lower(*args, **kwargs).compile())


def steady_seconds(fn, reps=5):
    """Median seconds of ``fn``, called after its first (compiling) call,
    each call waited for."""
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t)
    return float(np.median(times))


def peak_hbm():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


# -- phase 2: the placement sweep ---------------------------------------------
def zero_sigma(steps):
    return [
        replace(
            s,
            compute=S.Dist(s.compute.median, 0.0),
            fetch=S.Dist(s.fetch.median, 0.0),
        )
        for s in steps
    ]


def sim_platforms(sigma0=False, keep_warm=None):
    out = []
    for p in S.paper_platforms():
        if sigma0:
            p = replace(p, cold_start=S.Dist(p.cold_start.median, 0.0))
        if keep_warm is not None:
            p = replace(p, keep_warm_s=keep_warm)
        out.append(p)
    return out


@contextlib.contextmanager
def recorded_sweep_calls():
    """Record the arguments of every ``jaxsim._sweep`` call made inside,
    so the program that actually ran can be compiled again and read."""
    calls, real = [], jaxsim._sweep

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    jaxsim._sweep = spy
    try:
        yield calls
    finally:
        jaxsim._sweep = real


def numpy_sweep(sim, spec, placements):
    """The numpy backend on the same sweep, (seeds, placements, requests)."""
    return np.stack(
        [
            sim.simulate(replace(spec, steps=tuple(p)), backend="numpy")
            for p in placements
        ],
        axis=1,
    )


def timed_sweep(sim, spec, placements, dtype, reps=2):
    t = time.perf_counter()
    out = sim.simulate_placements(spec, placements, dtype=dtype)
    first = time.perf_counter() - t
    steady = steady_seconds(
        lambda: sim.simulate_placements(spec, placements, dtype=dtype), reps
    )
    log(
        f"  {out.shape} {np.dtype(dtype).name}: first call {first:.3f} s "
        f"(compile + run), steady {steady:.4f} s "
        f"({out.size / steady:.0f} simulated requests/s)"
    )
    check(np.isfinite(out).all(), "sweep totals not finite")
    return out


def check_spread_parity(name, jx, npy):
    med_gap = np.abs(np.median(jx, axis=(0, 2)) / np.median(npy, axis=(0, 2)) - 1)
    p99_gap = abs(np.percentile(jx, 99) / np.percentile(npy, 99) - 1)
    log(
        f"  {name}: max per-placement median gap {med_gap.max():.5f}, "
        f"pooled p99 gap {p99_gap:.5f} (limit {SPREAD_REL})"
    )
    check(med_gap.max() <= SPREAD_REL, f"{name}: median gap {med_gap.max()}")
    check(p99_gap <= SPREAD_REL, f"{name}: p99 gap {p99_gap}")


def check_sigma0_parity(name, sim, spec, placements):
    jx = sim.simulate_placements(spec, placements, dtype=np.float64)
    gap = float(np.abs(jx - numpy_sweep(sim, spec, placements)).max())
    log(f"  {name}: sigma=0 f64 max |jax - numpy| {gap:.3e} (limit {SIGMA0_ATOL})")
    check(gap <= SIGMA0_ATOL, f"{name}: sigma=0 gap {gap}")


def score_once(seeds, n_placements, n):
    """One ``PlacementScorer(backend="jax")`` decision over the Fig-4
    chain's candidate placements, against the numpy backend's scores."""
    steps = S.document_workflow_fig4()
    nodes = {s.name: s for s in steps}
    edges = [(a.name, b.name) for a, b in zip(steps, steps[1:])]
    plats = [p.name for p in S.paper_platforms()]
    # the edge step stays put; the other three range over every platform
    placements = [
        {steps[0].name: steps[0].platform}
        | {s.name: p for s, p in zip(steps[1:], combo)}
        for combo in itertools.product(plats, repeat=len(steps) - 1)
    ][:n_placements]
    by_name = {s.name: s for s in steps}
    costs = PlacementCosts(
        fetch_s=lambda n, p, d: by_name[n].fetch.median,
        compute_s=lambda n, p: by_name[n].compute.median * (1 + plats.index(p) / 4),
        transfer_s=lambda a, b, size: 0.0 if a == b else 0.05,
    )
    out = {}
    for backend in ("jax", "numpy"):
        scorer = PlacementScorer(n_requests=n, seeds=seeds, backend=backend)
        t = time.perf_counter()
        out[backend] = scorer.distributions(nodes, edges, placements, costs)
        log(f"  scorer {backend}: {time.perf_counter() - t:.3f} s")
    check(out["jax"].shape == (len(placements), len(seeds) * n), "scorer shape")
    check(np.isfinite(out["jax"]).all(), "scorer totals not finite")
    gap = np.abs(np.median(out["jax"], axis=1) / np.median(out["numpy"], axis=1) - 1)
    log(f"  scorer: max median gap jax vs numpy {gap.max():.5f} (limit {SPREAD_REL})")
    check(gap.max() <= SPREAD_REL, f"scorer median gap {gap.max()}")


def check_cold_masks(rows, n, seed):
    """The kernel's mask against both references, in a regime where the
    mask recurses: keep_warm sits between most warm and cold gaps."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    t0 = jnp.cumsum(1.0 * (0.5 + jax.random.uniform(k1, (n,))))
    warm = t0[None, :] + 0.3 * jax.random.uniform(k2, (rows, n))
    cold = warm + 0.3 * jax.random.uniform(k3, (rows, n))
    kw = 0.95
    flips = int(jnp.sum((t0[1:] - warm[:, :-1] > kw) & ~(t0[1:] - cold[:, :-1] > kw)))
    check(flips > 0, "mask regime has no flip bits")
    for dtype in (jnp.float32, jnp.float64):
        with jax.enable_x64(dtype == jnp.float64):
            args = (t0.astype(dtype), warm.astype(dtype), cold.astype(dtype), dtype(kw))
            name = f"cold_scan {jnp.dtype(dtype).name}"
            assert_kernel_compiled(name, ops.cold_scan.lower(*args).compile())
            got = np.asarray(ops.cold_scan(*args))
            par = np.asarray(cold_scan_parallel(*args))
            want = np.asarray(ref.cold_scan_ref(*args))
        log(
            f"  cold mask {jnp.dtype(dtype).name} ({rows}x{n}, {flips} flip bits, "
            f"{want.mean():.3f} cold): kernel == parallel {np.array_equal(got, par)}, "
            f"kernel == ref {np.array_equal(got, want)}"
        )
        check(np.array_equal(got, want), "kernel mask != cold_scan_ref")
        check(np.array_equal(got, par), "kernel mask != cold_scan_parallel")


def phase_sweep(seed, scorer_shape=SCORER_SHAPE, throughput_shape=THROUGHPUT_SHAPE,
                mask_shape=MASK_SHAPE):
    fig4 = S.document_workflow_fig4()

    n_seeds, n_pl, n = scorer_shape
    log(f"phase 2a: scorer shape {scorer_shape}")
    seeds = tuple(range(seed, seed + n_seeds))
    placements = candidate_placements(n_pl)
    spec = S.ExperimentSpec(placements[0], n_requests=n, seeds=seeds)
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=seed)
    with recorded_sweep_calls() as calls:
        jx = timed_sweep(sim, spec, placements, np.float32)
    assert_sweep_kernel(calls[0])
    check_spread_parity("2a spread", jx, numpy_sweep(sim, spec, placements))
    sim0 = S.WorkflowSimulator(sim_platforms(sigma0=True), seed=seed)
    check_sigma0_parity("2a", sim0, spec, [zero_sigma(p) for p in placements])
    score_once(seeds, n_pl, n)

    n_seeds, n_pl, n = throughput_shape
    log(f"phase 2b: throughput shape {throughput_shape}")
    placements = candidate_placements(n_pl)
    spec = S.ExperimentSpec(
        placements[0], n_requests=n, seeds=tuple(range(seed, seed + n_seeds))
    )
    with recorded_sweep_calls() as calls:
        jx = timed_sweep(sim, spec, placements, np.float32, reps=1)
    peak, limit = peak_hbm()
    args, kwargs = calls[0]
    with jax.enable_x64(True):
        mem = jaxsim._sweep.lower(*args, **kwargs).compile().memory_analysis()
    need = sum(
        getattr(mem, f"{k}_size_in_bytes") for k in ("argument", "output", "temp")
    )
    log(
        f"  HBM: peak in use {peak} bytes of {limit}; the compiled sweep "
        f"needs {need} bytes ({mem.temp_size_in_bytes} temporaries)"
    )
    check(limit is None or need < limit, "the sweep does not fit the chip")
    check_spread_parity(
        "2b spread (first placement)", jx[:, :1], numpy_sweep(sim, spec, placements[:1])
    )

    n_seeds, n_pl, n = scorer_shape
    log(
        f"phase 2c: cold regime, keep_warm {COLD_KEEP_WARM_S} s, "
        f"interarrival {COLD_INTERARRIVAL_S} s, {scorer_shape}"
    )
    placements = candidate_placements(n_pl)
    spec = S.ExperimentSpec(
        placements[0],
        n_requests=n,
        interarrival_s=COLD_INTERARRIVAL_S,
        seeds=tuple(range(seed, seed + n_seeds)),
    )
    simc = S.WorkflowSimulator(sim_platforms(keep_warm=COLD_KEEP_WARM_S), seed=seed)
    jx = timed_sweep(simc, spec, placements, np.float32)
    check_spread_parity("2c spread", jx, numpy_sweep(simc, spec, placements))
    simc0 = S.WorkflowSimulator(
        sim_platforms(sigma0=True, keep_warm=COLD_KEEP_WARM_S), seed=seed
    )
    check_sigma0_parity("2c", simc0, spec, [zero_sigma(p) for p in placements])
    check_cold_masks(*mask_shape, seed)
    # the Fig-4 chain itself in the cold regime: request 0 is cold and the
    # edge step alternates, so the kernel's mask decides every total
    check_sigma0_parity("2c fig4", simc0, replace(spec, steps=tuple(fig4)),
                        [zero_sigma(fig4)])


# -- phase 3: the served workflow -----------------------------------------------
def prefill_program(cfg, params, prompt_len):
    tokens = {"tokens": jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)}
    t = time.perf_counter()
    step = jax.jit(lambda p, b: M.prefill(cfg, p, b))
    compiled = step.lower(params, tokens).compile()
    return compiled, time.perf_counter() - t


def compare_logits(name, got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{name}: shapes {got.shape} {want.shape}")
    check(np.isfinite(got).all() and np.isfinite(want).all(), f"{name}: not finite")
    max_abs = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    log(
        f"  {name}: max |diff| {max_abs:.4f}, max |ref| {scale:.4f}, "
        f"max rel {max_abs / scale:.5f} (bf16 tolerance {BF16_TOL}); "
        f"top-1 equal {np.array_equal(got.argmax(-1), want.argmax(-1))}"
    )
    check(max_abs <= BF16_TOL * scale, f"{name}: {max_abs} > {BF16_TOL} * {scale}")


def init_params(cfg, seed):
    """The model's weights from the seed, drawn on the device by one
    compiled program (leaf by leaf, each op would compile on its own)."""
    t = time.perf_counter()
    params = jax.jit(M.init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    dt = time.perf_counter() - t
    log(f"  {n} parameters (f32) drawn from seed {seed} in {dt:.1f} s")
    return params


def phase_serve(seed, cfg=None, prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS):
    cfg = (cfg or get_config("qwen3-1.7b")).replace(use_pallas=True)
    log(
        f"phase 3: served workflow, {cfg.name} ({cfg.num_layers} layers, "
        f"d_model {cfg.d_model}, vocab {cfg.vocab_size})"
    )
    params = init_params(cfg, seed)
    prompt = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (1, prompt_len), 1, cfg.vocab_size
    )
    pallas, pallas_s = prefill_program(cfg, params, prompt_len)
    assert_kernel_compiled("prefill (Pallas flash attention)", pallas)
    plain, plain_s = prefill_program(cfg.replace(use_pallas=False), params, prompt_len)
    log(f"  prefill compile: Pallas {pallas_s:.1f} s, jnp {plain_s:.1f} s")
    compare_logits(
        "prefill logits, Pallas vs jnp",
        pallas(params, {"tokens": prompt})[0],
        plain(params, {"tokens": prompt})[0],
    )
    del pallas, plain

    out = federated_serving.main(
        cfg, params, prompt_len=prompt_len, new_tokens=new_tokens, seed=seed
    )
    log(
        f"  compile: workflow steps {out['compile_s']:.1f} s, "
        f"engine steps {out['engine_compile_s']:.1f} s (apart from the times above)"
    )
    check(
        len(out["workflow"]) == federated_serving.WORKFLOW_REQUESTS,
        "workflow requests lost",
    )
    check(
        len(out["engine"]) == federated_serving.ENGINE_REQUESTS, "engine requests lost"
    )
    for rec in out["workflow"] + out["engine"]:
        toks = np.asarray(rec["tokens"])
        check(len(toks) == new_tokens, f"{len(toks)} tokens, expected {new_tokens}")
        check(((toks >= 0) & (toks < cfg.vocab_size)).all(), "token out of vocab")
    peak, limit = peak_hbm()
    log(f"  peak HBM in use {peak} bytes of {limit}")


# -- four chips: the sharded decode step -----------------------------------------
def phase_four_chips(seed, cfg=None, prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS):
    cfg = cfg or get_config("qwen3-1.7b")
    check(len(jax.devices()) >= 4, "--four-chips needs 4 devices")
    log(f"four chips: decode step of {cfg.name} on a 4-way model-parallel platform")
    max_len = prompt_len + new_tokens
    params = init_params(cfg, seed)
    prompt = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (1, prompt_len), 1, cfg.vocab_size
    )
    logits, caches = jax.jit(lambda p, b: M.prefill(cfg, p, b))(
        params, {"tokens": prompt}
    )
    caches = pad_cache(caches, max_len, prompt_len, cfg=cfg)
    token = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    cur = jnp.asarray(prompt_len, jnp.int32)

    def decode(p, t, c, i):
        return M.decode_step(cfg, p, t, c, i)

    step = jax.jit(decode)
    one_chip = step(params, token, caches, cur)[0]
    one_s = steady_seconds(lambda: step(params, token, caches, cur))

    mesh = make_host_mesh(model_parallel=4)
    platform = bind_sharding(Platform("decode-pod", "us-west"), mesh)
    rules = platform.rules

    def placed(tree, pspecs):
        return jax.device_put(
            tree,
            jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                pspecs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            ),
        )

    params4 = placed(params, prm.param_pspecs(M.param_defs(cfg), rules, mesh))
    caches4 = placed(caches, M.spec_pspecs(M.cache_defs(cfg, 1, max_len), rules, mesh))
    del params
    # the platform wrapper binds the mesh and rules around the step, so the
    # model's sharding constraints apply when the step is traced
    wrapped = PlatformWrapper(platform, jax.jit(decode), name="decode")
    t = time.perf_counter()
    sharded, _ = wrapped(params4, token, caches4, cur)
    sharded.block_until_ready()
    log(f"  sharded decode: first call {time.perf_counter() - t:.1f} s (compile + run)")
    four_s = steady_seconds(lambda: wrapped(params4, token, caches4, cur))
    log(
        f"  decode step, steady median of 5: one chip {one_s * 1e3:.2f} ms, "
        f"four chips {four_s * 1e3:.2f} ms"
    )
    leaves = jax.tree_util.tree_leaves(params4)
    devices = {d for x in leaves for d in x.sharding.device_set}
    log(f"  parameters span {len(devices)} devices")
    check(len(devices) == 4, "parameters are not spread over the 4 chips")
    for d in jax.devices()[:4]:
        stats = d.memory_stats() or {}
        log(f"  {d}: {stats.get('bytes_in_use')} bytes in use")
    compare_logits("decode logits, 4-chip vs 1-chip", sharded, one_chip)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the decode step sharded over four chips, against one chip",
    )
    args = ap.parse_args(argv)
    device = require_tpu()
    enable_compile_cache()
    t = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_sweep(args.seed)
        phase_serve(args.seed)
    log(f"all phases passed in {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

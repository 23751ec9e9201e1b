"""Federated serving: prefill/decode disaggregation as a GeoFF workflow.

Two "pods" (platforms): a prefill pod and a decode pod. Each request is a
two-step workflow — prefill builds the KV cache and ships it by reference;
the decode pod (pre-warmed via the poke) streams tokens with continuous
batching. The placement optimizer decides whether decode should run on the
pod holding the cache (function shipping, §4.3/§5.3).

    PYTHONPATH=src python examples/federated_serving.py

``main(cfg, ...)`` serves any decoder config (``chip_smoke.py`` runs it at
the full width of qwen3-1.7b); with no arguments it runs the reduced
smoke config. Both step programs are compiled ahead of the requests, so
compile seconds are reported apart from time to first token (TTFT) and
decode-step time.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    Deployment,
    Platform,
    PlatformRegistry,
    PlacementCosts,
    StepSpec,
    WorkflowSpec,
    place_chain,
)
from repro.configs.registry import smoke_config
from repro.models import model as M
from repro.serving import Request, ServingEngine, pad_cache


WORKFLOW_REQUESTS, ENGINE_REQUESTS = 3, 6


def main(cfg=None, params=None, prompt_len=8, new_tokens=8, seed=0):
    """Serve ``WORKFLOW_REQUESTS`` through the two-platform workflow and
    ``ENGINE_REQUESTS`` through the continuous-batching engine. ``params``
    defaults to ``M.init_params(cfg, PRNGKey(seed))``. Returns the
    measurements: compile seconds, and per request TTFT, decode-step
    seconds and tokens."""
    cfg = cfg or smoke_config("qwen3-1.7b")
    if params is None:
        params = M.init_params(cfg, jax.random.PRNGKey(seed))
    max_len = prompt_len + new_tokens
    vocab_hi = min(cfg.vocab_size, 200)
    out = {"workflow": [], "engine": []}

    reg = PlatformRegistry()
    reg.register(Platform("prefill-pod", "us-east", native_prefetch=True))
    reg.register(Platform("decode-pod", "us-west", native_prefetch=True))
    with Deployment(reg) as dep:
        dep.store.network.set_link("us-east", "us-west", 0.02, 200e6)

        # compile both step programs ahead of traffic: the requests below
        # run the compiled executables, so no request pays a compile
        t0 = time.perf_counter()
        tok_spec = {"tokens": jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)}
        _prefill = (
            jax.jit(lambda p, b: M.prefill(cfg, p, b)).lower(params, tok_spec).compile()
        )
        cache_spec = M.spec_structs(M.cache_defs(cfg, 1, max_len))
        _decode = (
            jax.jit(lambda p, t, c, i: M.decode_step(cfg, p, t, c, i))
            .lower(
                params,
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
                cache_spec,
                jax.ShapeDtypeStruct((), jnp.int32),
            )
            .compile()
        )
        out["compile_s"] = time.perf_counter() - t0

        def prefill_fn(payload, data):
            prompt = payload
            logits, caches = _prefill(params, {"tokens": jnp.asarray(prompt)[None]})
            first_tok = int(jnp.argmax(logits[0]))
            t_first = time.perf_counter()
            caches = pad_cache(caches, max_len, len(prompt), cfg=cfg)
            key = f"kv/{hash(prompt.tobytes()) & 0xFFFF}"
            dep.store.put(
                key, jax.tree_util.tree_map(np.asarray, caches), region="us-east"
            )
            return {
                "first_tok": first_tok,
                "t_first": t_first,
                "kv_key": key,
                "pos": len(prompt),
            }

        def decode_fn(payload, data):
            host_caches, _ = dep.store.get(payload["kv_key"], "us-west")
            caches = jax.tree_util.tree_map(jnp.asarray, host_caches)
            tok, cur = payload["first_tok"], payload["pos"]
            toks, step_s = [tok], []
            for _ in range(new_tokens - 1):
                t = time.perf_counter()
                logits, caches = _decode(
                    params,
                    jnp.asarray([[tok]], jnp.int32),
                    caches,
                    jnp.asarray(cur, jnp.int32),
                )
                tok = int(jnp.argmax(logits[0]))
                step_s.append(time.perf_counter() - t)
                toks.append(tok)
                cur += 1
            return {"tokens": toks, "t_first": payload["t_first"], "step_s": step_s}

        dep.deploy("prefill", prefill_fn, ["prefill-pod"])
        dep.deploy("decode", decode_fn, ["prefill-pod", "decode-pod"])

        # --- placement: should decode run where the KV cache lives? ---------
        spec = WorkflowSpec(
            (StepSpec("prefill", "prefill-pod"), StepSpec("decode", "decode-pod")),
            "serve",
        )
        costs = PlacementCosts(
            # cache ships over DCN if decode runs remote from the cache
            fetch_s=lambda n, p, d: (
                0.15 if (n, p) == ("decode", "decode-pod") else 0.01
            ),
            compute_s=lambda n, p: 0.2,
            transfer_s=lambda a, b, s: 0.0 if a == b else 0.02,
        )
        placed = place_chain(spec, {"decode": ["prefill-pod", "decode-pod"]}, costs)
        print(
            f"placement optimizer: decode -> {placed.steps[1].platform} "
            "(ships the function to the cache)"
        )

        # --- run a few requests through the disaggregated workflow ----------
        rng = np.random.default_rng(seed)
        for i in range(WORKFLOW_REQUESTS):
            prompt = rng.integers(1, vocab_hi, size=prompt_len).astype(np.int32)
            t_req = time.perf_counter()
            r = dep.run(placed, prompt)
            res = r.outputs
            rec = {
                "ttft_s": res["t_first"] - t_req,
                "decode_step_s": float(np.mean(res["step_s"] or [0.0])),
                "tokens": res["tokens"],
                "total_s": r.total_s,
            }
            out["workflow"].append(rec)
            print(
                f"req {i}: {r.total_s * 1e3:7.1f} ms  "
                f"TTFT {rec['ttft_s'] * 1e3:7.1f} ms  "
                f"decode step {rec['decode_step_s'] * 1e3:6.2f} ms  "
                f"tokens={res['tokens'][:8]}"
            )

    # --- same model under the continuous-batching engine ---------------------
    print("\ncontinuous batching on one pod:")
    eng = ServingEngine(cfg, params, max_batch=3, max_len=max_len)
    t0 = time.perf_counter()
    eng.prewarm(prompt_len)
    out["engine_compile_s"] = time.perf_counter() - t0
    reqs = [
        Request(
            i,
            rng.integers(1, vocab_hi, size=prompt_len).astype(np.int32),
            max_new_tokens=new_tokens,
        )
        for i in range(ENGINE_REQUESTS)
    ]
    t0 = time.perf_counter()
    for req in reqs:
        req.t_submit = t0
        eng.submit(req)
    stats = eng.run()
    dt = time.perf_counter() - t0
    for req in reqs:
        rec = {
            "ttft_s": req.t_first_token - req.t_submit,
            "decode_step_s": (req.t_done - req.t_first_token)
            / max(len(req.tokens) - 1, 1),
            "tokens": req.tokens,
        }
        out["engine"].append(rec)
        print(
            f"  req {req.rid}: TTFT {rec['ttft_s'] * 1e3:7.1f} ms  "
            f"decode step {rec['decode_step_s'] * 1e3:6.2f} ms  "
            f"({len(req.tokens)} tokens)"
        )
    print(
        f"  {stats['done']} requests in {dt * 1e3:.0f} ms "
        f"({stats['decode_steps']} decode steps, "
        f"{stats['prefills']} prefills, mean TTFT "
        f"{np.mean(stats['ttft_s']) * 1e3:.0f} ms)"
    )
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

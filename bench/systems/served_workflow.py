"""A decoder served as a two-step GeoFF workflow: prefill on one platform,
decode on another, the KV cache handed over through the object store.

This is the application a GeoFF user deploys (the workflow of
``examples/federated_serving.py``), written against the program's APIs
only: ``M.prefill`` and ``M.decode_step`` compiled ahead of the window,
``pad_cache``, ``Deployment.run``, ``dep.store.put/get`` and greedy
``argmax``. Device arrays go to the store as they are. Decoding is per
request at batch 1.

The client is an open loop: each request of the mix's schedule is sent at
its due time from a pool of client threads, and its latency runs from the
due time to the workflow's result in the client's hands.
"""

from __future__ import annotations

import gc
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import flops, traffic
from bench.harness import DRAIN_SECONDS, load_json, span

SAMPLE_TOKENS = 256  # served tokens the reference checks, at the least


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` uint32 words of key material from a seed of any size."""
    return traffic.rng(seed, 8).integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)


def init_weights(arch, seed: int):
    """The model's weights from the seed, in float32 as the program holds
    them, drawn on the device by one jitted program: normal with a
    1/sqrt(fan-in) scale (scale 1 for the embedding), norm gains at their
    identity. The fan-in skips the stacked layer axis."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.params import ParamDef

    leaves, tree = jax.tree_util.tree_flatten(
        M.param_defs(arch), is_leaf=lambda x: isinstance(x, ParamDef)
    )

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, d in zip(keys, leaves):
            if d.init == "zeros":
                out.append(jnp.zeros(d.shape, jnp.float32))
                continue
            if d.init not in ("normal", "embed"):
                raise ValueError(f"no draw for a parameter initialised {d.init!r}")
            fan = d.shape[1:] if d.axes[0] == "layers" else d.shape
            scale = 1.0 if d.init == "embed" else float(fan[0]) ** -0.5
            out.append(jax.random.normal(k, d.shape, jnp.float32) * scale)
        return jax.tree_util.tree_unflatten(tree, out)

    key = jax.random.wrap_key_data(seed_words(seed, 4), impl="rbg")
    params = jax.jit(draw)(key)
    jax.block_until_ready(params)
    return params


def _named(fn, name):
    fn.__name__ = fn.__qualname__ = name
    return fn


class System:
    def __init__(self, cell, cfg, mix, seed, ref, log):
        if mix["loop"] != "open":
            raise ValueError(f"served_workflow needs an open loop, got {mix['loop']!r}")
        self.cell, self.cfg, self.mix, self.seed = cell, cfg, mix, seed
        self.ref, self.log = ref, log
        self.traced = False
        self._lock = threading.Lock()
        self.steps = []  # (t_end, model flops) of every prefill and decode step

    # -- set-up -----------------------------------------------------------------
    def setup(self, phases):
        import jax
        import jax.numpy as jnp

        from repro.configs.base import ArchConfig
        from repro.core import (
            Deployment,
            ObjectStore,
            Platform,
            PlacementCosts,
            PlatformRegistry,
            StepSpec,
            WorkflowSpec,
            place_chain,
        )
        from repro.models import model as M
        from repro.serving import pad_cache

        t = time.perf_counter()
        a = self.cfg["arch"]
        arch = ArchConfig(**dict(a, block_pattern=tuple(a["block_pattern"])))
        self.arch = arch
        self.params = init_weights(arch, self.seed)
        phases["weights_s"] = time.perf_counter() - t

        i32 = jnp.int32
        self.prefill, self.decode = {}, {}
        for T, cap in traffic.shapes(self.mix):
            t = time.perf_counter()
            fn = _named(lambda p, b: M.prefill(arch, p, b), f"serve_prefill_{T}")
            tok = {"tokens": jax.ShapeDtypeStruct((1, T), i32)}
            self.prefill[T] = jax.jit(fn).lower(self.params, tok).compile()
            phases[f"compile_prefill_{T}_s"] = time.perf_counter() - t
            t = time.perf_counter()
            fn = _named(
                lambda p, x, c, i: M.decode_step(arch, p, x, c, i),
                f"serve_decode_{cap}",
            )
            self.decode[cap] = (
                jax.jit(fn)
                .lower(
                    self.params,
                    jax.ShapeDtypeStruct((1, 1), i32),
                    M.spec_structs(M.cache_defs(arch, 1, cap)),
                    jax.ShapeDtypeStruct((), i32),
                )
                .compile()
            )
            phases[f"compile_decode_{cap}_s"] = time.perf_counter() - t
        t = time.perf_counter()
        def greedy(lg):
            # the token and its logit, read back together
            return jnp.stack([jnp.argmax(lg[0]).astype(jnp.float32), jnp.max(lg[0])])

        greedy = _named(greedy, "serve_greedy")
        self.greedy = (
            jax.jit(greedy)
            .lower(jax.ShapeDtypeStruct((1, arch.vocab_size), jnp.float32))
            .compile()
        )
        phases["compile_greedy_s"] = time.perf_counter() - t

        dcfg = self.cfg["deployment"]
        reg = PlatformRegistry()
        for p in dcfg["platforms"]:
            reg.register(
                Platform(p["name"], p["region"], native_prefetch=p["native_prefetch"])
            )
        link = dcfg["link"]
        reg.network.set_link(link["a"], link["b"], link["rtt_s"], link["bytes_per_s"])
        store = ObjectStore(reg.network, enforce_latency=dcfg["enforce_latency"])
        self.dep = dep = Deployment(reg, store=store)
        region = {p["name"]: p["region"] for p in dcfg["platforms"]}
        home = region[dcfg["steps"]["prefill"][0]]

        def prefill_handler(req, data):
            T, cap = len(req["prompt"]), len(req["prompt"]) + self.max_new
            with span("bench.prefill", self.traced):
                batch = {"tokens": req["prompt"][None]}
                logits, caches = self.prefill[T](self.params, batch)
                first, first_logit = self._pick(logits)
            self._step(flops.prefill_flops(self.cfg, T))
            with span("bench.pad_cache", self.traced):
                caches = pad_cache(caches, cap, T, cfg=arch)
            key = f"kv/{req['rid']}"
            with span("bench.store_put", self.traced):
                dep.store.put(key, caches, region=home)
            return {"rid": req["rid"], "key": key, "first": first, "pos": T,
                    "first_logit": first_logit, "cap": cap,
                    "new_tokens": req["new_tokens"]}

        def decode_handler_at(where):
            def decode_handler(msg, data):
                with span("bench.store_get", self.traced):
                    caches, _ = dep.store.get(msg["key"], where)
                dep.store.delete(msg["key"])
                step = self.decode[msg["cap"]]
                tok, cur = msg["first"], msg["pos"]
                toks, vals = [tok], [msg["first_logit"]]
                for _ in range(msg["new_tokens"] - 1):
                    with span("bench.decode_step", self.traced):
                        x = np.array([[tok]], np.int32)
                        logits, caches = step(self.params, x, caches, np.int32(cur))
                        tok, val = self._pick(logits)
                    self._step(flops.decode_flops(self.cfg, cur + 1))
                    toks.append(tok)
                    vals.append(val)
                    cur += 1
                return {"rid": msg["rid"], "tokens": toks, "logits": vals}

            return decode_handler

        self.max_new = max(self.mix["new_tokens"]["values"])
        for pname in dcfg["steps"]["prefill"]:
            dep.deploy("prefill", prefill_handler, [pname])
        for pname in dcfg["steps"]["decode"]:
            dep.deploy("decode", decode_handler_at(region[pname]), [pname])
        pc = dcfg["placement_costs"]
        costs = PlacementCosts(
            fetch_s=lambda n, p, d: pc["fetch_s"].get(
                f"{n}@{p}", pc["fetch_s"]["default"]
            ),
            compute_s=lambda n, p: pc["compute_s"],
            transfer_s=lambda a, b, s: 0.0 if a == b else pc["transfer_cross_s"],
        )
        chain = WorkflowSpec(
            tuple(StepSpec(name, plats[0]) for name, plats in dcfg["steps"].items()),
            "serve",
        )
        self.placed = place_chain(chain, dict(dcfg["steps"]), costs)
        where = ", ".join(f"{s.name}->{s.platform}" for s in self.placed.steps)
        self.log(f"placement: {where}")

        t = time.perf_counter()
        gen = traffic.rng(self.seed, 9)
        for T, _ in traffic.shapes(self.mix):
            prompt = gen.integers(0, arch.vocab_size, T, dtype=np.int32)
            req = {"rid": f"warm{T}", "prompt": prompt, "new_tokens": 2}
            out = dep.run(self.placed, req).outputs
            if out is None or len(out["tokens"]) != 2 or len(out["logits"]) != 2:
                raise RuntimeError(f"warm-up request at {T} tokens failed: {out}")
        phases["warmup_s"] = time.perf_counter() - t
        with self._lock:
            self.steps.clear()

    def _pick(self, logits):
        """Greedy: the served token and its logit."""
        tok, val = np.asarray(self.greedy(logits)).tolist()
        return int(tok), val

    def _step(self, model_flops):
        with self._lock:
            self.steps.append((time.perf_counter(), model_flops))

    # -- the window ----------------------------------------------------------------
    def window(self, seconds, prof):
        from repro.obs import Tracer, instrument

        sched = traffic.open_loop(self.mix, self.seed, seconds, self.arch.vocab_size)
        self.tracer = None
        if prof is not None:
            self.traced = True
            tracer = Tracer(max_traces=len(sched) + 8, sample=0)
            self.tracer = instrument(self.dep, tracer)
        s0 = self.dep.store.stats_snapshot()
        recs = [None] * len(sched)
        pool = ThreadPoolExecutor(
            max_workers=min(256, len(sched)), thread_name_prefix="client"
        )
        t0 = time.perf_counter() + 0.01
        deadline = t0 + seconds + DRAIN_SECONDS
        if prof is not None:
            prof.delay = max(0.0, t0 + prof.delay - time.perf_counter())
            prof.start()
        futs = []
        for i, r in enumerate(sched):
            due = t0 + r["due"]
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            futs.append(pool.submit(self._client, recs, i, r, due, deadline))
        for f in futs:
            f.result()
        pool.shutdown(wait=True)
        s1 = self.dep.store.stats_snapshot()
        self.store_bytes = sum(s1[k] - s0[k] for k in ("bytes_in", "bytes_out"))
        self.recs = recs
        failed = sum(1 for r in recs if r["tokens"] is None)
        return {"attempted": len(recs), "failed": failed, "records": recs}

    def _client(self, recs, i, r, due, deadline):
        t_sub = time.perf_counter()
        req = {"rid": i, "prompt": r["prompt"], "new_tokens": r["new_tokens"]}
        tokens, logits, err = None, None, None
        try:
            res = self.dep.run(self.placed, req, timeout_s=max(0.1, deadline - t_sub))
            if res.outputs is None:
                err = "timeout"
            elif len(res.outputs["tokens"]) != r["new_tokens"]:
                err = f"{len(res.outputs['tokens'])} tokens for {r['new_tokens']}"
            else:
                tokens, logits = res.outputs["tokens"], res.outputs["logits"]
        except Exception as exc:  # a failed request is a miss, not a crash
            err = repr(exc)
        t_done = time.perf_counter()
        recs[i] = {
            "due": due,
            "late_s": t_sub - due,
            "latency_s": (t_done - due) if tokens is not None else float("inf"),
            "prompt": r["prompt"],
            "new_tokens": r["new_tokens"],
            "tokens": tokens,
            "logits": logits,
            "error": err,
        }

    def lateness_lines(self):
        late = np.array([r["late_s"] for r in self.recs]) * 1e3
        return [
            f"generator lateness ms: median {np.median(late):.3f} p99 "
            f"{np.percentile(late, 99):.3f} max {late.max():.3f} "
            f"over {len(late)} requests"
        ]

    def end_to_end(self, result):
        from bench.harness import quantile_ms

        lat = [r["latency_s"] for r in result["records"]]
        return {
            "workflow_p50_ms": quantile_ms(lat, 0.5),
            "workflow_p90_ms": quantile_ms(lat, 0.9),
        }

    def trace_records(self, t_trace):
        from repro.obs import extract_critical_path

        waits = []
        for tr in self.tracer.traces():
            attr = extract_critical_path(tr).attribution
            waits.append(sum(v for k, v in attr.items() if k != "compute"))
        done = sum(1 for r in self.recs if r["tokens"] is not None)
        lo, hi = t_trace
        with self._lock:
            fl = sum(f for t, f in self.steps if lo <= t <= hi)
        return {
            "engine_wait_s": waits,
            "store_bytes": self.store_bytes,
            "requests_done": done,
            "model_flops_in_trace": fl,
        }

    def ref_shape(self):
        """(padded length, read positions) of the reference for this mix."""
        return traffic.shapes(self.mix)[-1][1], self.max_new

    # -- after the window -------------------------------------------------------------
    def release(self):
        self.dep.shutdown()
        self.prefill = self.decode = self.greedy = self.dep = self.tracer = None
        gc.collect()

    def reseed(self, seed):
        """Draw the weights of another seed into the same compiled programs."""
        self.seed = seed
        self.params = init_weights(self.arch, seed)

    def sample(self, result):
        """The finished requests the reference checks, drawn from the seed:
        the longest first, then others until ``SAMPLE_TOKENS`` served
        tokens."""
        recs = [r for r in result["records"] if r["tokens"] is not None]
        if not recs:
            return []
        size = [len(r["prompt"]) + len(r["tokens"]) for r in recs]
        longest = int(np.argmax(size))
        order = [longest] + [i for i in traffic.rng(self.seed, 7).permutation(len(recs))
                             if i != longest]
        pick, n_tok = [], 0
        for i in order:
            if n_tok >= SAMPLE_TOKENS:
                break
            pick.append((recs[i]["prompt"], recs[i]["tokens"], recs[i]["logits"]))
            n_tok += len(recs[i]["tokens"])
        return pick

    def readings(self, result, control=False):
        """The numbers the comparison reads; with ``control``, those of the
        control in the program's place on the same prompts and tokens."""
        pick = self.sample(result)
        if not pick:
            return {"served_logit_err_sd": float("inf")}
        t = time.perf_counter()
        errs = (self.ref.control_logit_err if control else self.ref.served_logit_err)(
            self.cfg, self.params, pick, self.ref_shape()
        )
        self.log(f"reference{' control' if control else ''}: {len(pick)} requests, "
                 f"{sum(len(p[1]) for p in pick)} served tokens, "
                 f"{time.perf_counter() - t:.1f} s")
        return {"served_logit_err_sd": float(np.max(errs))}

    def check(self, result, control=False):
        limits = load_json("limits", f"{self.cell}.json")
        checks = {"unanswered_requests": {"value": result["failed"], "limit": 0}}
        for name, v in self.readings(result, control).items():
            checks[name] = {"value": v, "limit": limits[name]}
        return checks

"""Quickstart: deploy a federated GeoFF workflow and watch pre-fetching work.

Three steps across three platforms (edge -> cloud A -> cloud B), the middle
one a real JAX model forward. Run:

    PYTHONPATH=src python examples/quickstart.py
"""

import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    DataRef,
    Deployment,
    Platform,
    PlatformRegistry,
    StepSpec,
    WorkflowSpec,
    bind_sharding,
)
from repro.configs.registry import smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import model as M


def main():
    # --- platforms (the federation) ----------------------------------------
    # Heterogeneous sharding configs: the edge node stays single-device
    # (bind_sharding drops the mesh), cloud regions carry a mesh over this
    # host's devices + the decode sharding rules — the platform wrapper
    # binds them as the ambient use_sharding context around every step.
    mesh = make_host_mesh(model_parallel=1)
    reg = PlatformRegistry()
    reg.register(
        bind_sharding(Platform("edge-berlin", "eu", kind="edge", native_prefetch=True))
    )
    reg.register(bind_sharding(Platform("cloud-us", "us", kind="cloud"), mesh=mesh))
    reg.register(bind_sharding(Platform("cloud-eu", "eu", kind="cloud"), mesh=mesh))
    with Deployment(reg) as dep:
        dep.store.enforce_latency = True  # real (slept) transfer time
        dep.store.network.set_link("eu", "us", 0.08, 10e6)

        # --- external data dependency (lives in the US) ---------------------
        rng = np.random.default_rng(0)
        dep.store.put(
            "emb/table", rng.normal(size=(256, 64)).astype(np.float32), region="us"
        )

        # --- one model, written once, deployable anywhere -------------------
        cfg = smoke_config("qwen3-1.7b")
        params = M.init_params(cfg, jax.random.PRNGKey(0))

        def tokenize(payload, data):
            toks = np.frombuffer(payload.encode(), np.uint8).astype(np.int32)
            return toks % (cfg.vocab_size - 1) + 1

        def forward(payload, data):
            logits, _ = M.prefill(cfg, params, {"tokens": jnp.asarray(payload)[None]})
            return np.asarray(logits[0])

        def project(payload, data):
            table = data["emb/table"]  # pre-fetched while forward ran
            return float(payload[:64] @ table[:64, 0])

        dep.deploy("tokenize", tokenize, ["edge-berlin"])
        dep.deploy("forward", forward, ["cloud-us", "cloud-eu"])
        dep.deploy("project", project, ["cloud-us"])

        # --- the per-request workflow spec (ad-hoc recomposition!) ----------
        wf = WorkflowSpec(
            (
                StepSpec("tokenize", "edge-berlin"),
                StepSpec("forward", "cloud-us"),
                StepSpec(
                    "project", "cloud-us", data_deps=(DataRef("emb/table", "us"),)
                ),
            ),
            "quickstart",
        )

        r1 = dep.run(wf, "hello federated serverless world")  # cold
        r2 = dep.run(wf, "hello federated serverless world")  # warm + prefetch
        print(f"cold run:  {r1.total_s * 1e3:8.1f} ms   result={r1.outputs:.4f}")
        print(f"warm run:  {r2.total_s * 1e3:8.1f} ms   result={r2.outputs:.4f}")
        print("per-step timeline (warm):")
        for step, t in r2.timeline.items():
            print(
                f"  {step:10s} warm={t['warm_s'] * 1e3:7.2f}ms "
                f"fetch={t['fetch_s'] * 1e3:7.2f}ms "
                f"compute={t['compute_s'] * 1e3:7.2f}ms"
            )

        # reroute the forward step to the EU cloud — no redeployment
        r3 = dep.run(wf.reroute("forward", "cloud-eu"), "hello again")
        print(f"rerouted:  {r3.total_s * 1e3:8.1f} ms   (forward now on cloud-eu)")
        print("prefetcher:", dep.prefetcher.stats)
        print("compile cache:", dep.cache.stats)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

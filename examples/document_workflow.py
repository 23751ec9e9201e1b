"""The paper's document-processing workflow (§4.2) on the real middleware —
restructured as a real fan-out DAG: after ``check``, the virus scan and the
OCR don't depend on each other, so they run in PARALLEL and join at
``e_mail`` (check -> virus || ocr -> e_mail). REAL handlers (hash checks,
byte scans, a toy JAX "OCR" conv model) and enforced network latencies.

Compares, on the same deployment:
  - the DAG with pre-fetching (branches overlap + fetches hidden),
  - the DAG without pre-fetching (parallel branches only),
  - the chain serialization of the same steps (the paper's §4.2 shape),
and the automated DAG placement (``place_dag`` wired into ``DagSpec``) that
ships OCR next to its data (§4.3/§5.3).

    PYTHONPATH=src python examples/document_workflow.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import DataRef, Deployment, Platform, PlatformRegistry
from repro.core.shipping import PlacementCosts
from repro.core.workflow import StepSpec, WorkflowSpec
from repro.dag import DagDeployment, DagSpec, DagStep, place_dag_spec


def build_platforms():
    reg = PlatformRegistry()
    reg.register(Platform("tinyfaas-edge", "eu", kind="edge", native_prefetch=True))
    reg.register(Platform("gcf", "eu", kind="cloud"))
    reg.register(Platform("lambda-us", "us", kind="cloud"))
    reg.register(Platform("lambda-eu", "eu2", kind="cloud"))
    return reg


def seed_store(store, rng):
    store.put("signatures/db", rng.bytes(2_000_000), region="us")
    store.put(
        "ocr/weights",
        rng.normal(size=(512, 8, 16)).astype(np.float32),
        region="us",
    )
    store.put("mail/template", b"Dear user, your document: ", region="us")


def check(payload, data):
    assert payload[:5] == b"%PDF-", "not a pdf"
    time.sleep(0.12)  # render/validate the document
    return payload


def virus(payload, data):
    db = data["signatures/db"]
    sig = db[:64]  # byte-scan against the signature db
    time.sleep(0.1)  # scan engine startup
    return {"clean": payload.find(sig) < 0}


def ocr(payload, data):
    w = jnp.asarray(data["ocr/weights"][:8])
    page = 64 * 64
    img = jnp.asarray(
        np.frombuffer(payload[:page], np.uint8).reshape(64, 64).astype(np.float32)
    )
    # toy conv "OCR" on the rendered page
    patches = img.reshape(8, 8, 8, 8).transpose(0, 2, 1, 3).reshape(64, 64)
    feats = jnp.einsum("pq,qkc->pkc", patches[:, :8], w)
    return {"text": float(jnp.sum(jax.nn.relu(feats)))}


def e_mail(payload, data):
    # fan-in: payload = {"virus": ..., "ocr": ...}
    template = data["mail/template"]
    return (
        template.decode()
        + f"{payload['ocr']['text']:.1f} (clean={payload['virus']['clean']})"
    )


def dag_spec(prefetch=True, ocr_platform="lambda-us"):
    return DagSpec(
        (
            DagStep("check", "tinyfaas-edge", prefetch=prefetch),
            DagStep(
                "virus",
                "gcf",
                data_deps=(DataRef("signatures/db", "us", 2_000_000),),
                prefetch=prefetch,
            ),
            DagStep(
                "ocr",
                ocr_platform,
                data_deps=(DataRef("ocr/weights", "us", 256 * 1024),),
                prefetch=prefetch,
            ),
            DagStep(
                "e_mail",
                "lambda-us",
                data_deps=(DataRef("mail/template", "us"),),
                prefetch=prefetch,
            ),
        ),
        (
            ("check", "virus"),
            ("check", "ocr"),
            ("virus", "e_mail"),
            ("ocr", "e_mail"),
        ),
        "docflow-dag",
    )


def deploy_all(dep):
    dep.store.enforce_latency = True
    for a, b in [("eu", "us"), ("eu2", "us"), ("eu", "eu2")]:
        dep.store.network.set_link(a, b, 0.06, 12e6)
    dep.deploy("check", check, ["tinyfaas-edge"])
    dep.deploy("virus", virus, ["gcf"])
    dep.deploy("ocr", ocr, ["lambda-us", "lambda-eu"])
    dep.deploy("e_mail", e_mail, ["lambda-us"])
    return dep


def main():
    rng = np.random.default_rng(7)
    pdf = b"%PDF-1.7 " + rng.bytes(int(1.2e6))

    # --- the DAG on the dataflow engine --------------------------------------
    with deploy_all(DagDeployment(build_platforms())) as dag:
        seed_store(dag.store, np.random.default_rng(11))
        for spec, label in [
            (dag_spec(True), "dag geoff (pre-fetching)"),
            (dag_spec(False), "dag baseline (no poke)"),
        ]:
            dag.run(spec, pdf)  # warm
            ts = [dag.run(spec, pdf).total_s for _ in range(3)]
            print(f"{label:28s} median {np.median(ts) * 1e3:7.1f} ms")
        print(
            "fan-in joins:",
            dag.stats["joins"],
            " pokes:",
            dict(sorted(dag.stats["pokes"].items())),
        )
        # per-edge slack (the timing controller's learning signal): each of
        # e_mail's two in-edges carries its own gap — virus finishes early,
        # ocr late — which is exactly what per-edge poke delays exploit
        edges = dag.timing.report()["edges"]
        for name in sorted(edges):
            print(f"  edge {name:18s} slack={edges[name]['slack_s'] * 1e3:7.1f} ms")

        # automated placement: ship OCR next to its data (§4.3, exact DP)
        ocr_fetch = {("ocr", "lambda-eu"): 1.9, ("ocr", "lambda-us"): 0.25}
        costs = PlacementCosts(
            fetch_s=lambda name, p, deps: ocr_fetch.get((name, p), 0.0),
            compute_s=lambda name, p: 0.15,
            transfer_s=lambda a, b, size: 0.05 if a == b else 0.4,
        )
        placed = place_dag_spec(
            dag_spec(True, "lambda-eu"), {"ocr": ["lambda-eu", "lambda-us"]}, costs
        )
        print("place_dag ships ocr to:", placed.node("ocr").platform)
        ts = [dag.run(placed, pdf).total_s for _ in range(3)]
        print(f"{'dag auto-placed':28s} median {np.median(ts) * 1e3:7.1f} ms")

        # where did the milliseconds go? trace one request and attribute
        # its critical path to cold/fetch/compute/transfer/poke-slack
        from repro.obs import Tracer, extract_critical_path, instrument

        tracer = instrument(dag, Tracer())
        dag.run(dag_spec(True), pdf)
        print(extract_critical_path(tracer.last()).format())

    # --- the chain serialization (a facade over the same dataflow core) ------
    with deploy_all(Deployment(build_platforms())) as chain:
        seed_store(chain.store, np.random.default_rng(11))

        def chain_email(payload, data):  # chain has no fan-in: adapt the join
            return e_mail({"virus": {"clean": True}, "ocr": payload}, data)

        def chain_virus(payload, data):  # chain threads the pdf through virus
            virus(payload, data)
            return payload

        chain.deploy("e_mail", chain_email, ["lambda-us"])
        chain.deploy("virus", chain_virus, ["gcf"])
        spec = WorkflowSpec(
            (
                StepSpec("check", "tinyfaas-edge"),
                StepSpec("virus", "gcf", data_deps=(DataRef("signatures/db", "us"),)),
                StepSpec(
                    "ocr", "lambda-us", data_deps=(DataRef("ocr/weights", "us"),)
                ),
                StepSpec(
                    "e_mail", "lambda-us", data_deps=(DataRef("mail/template", "us"),)
                ),
            ),
            "docflow",
        )
        chain.run(spec, pdf)
        ts = [chain.run(spec, pdf).total_s for _ in range(3)]
        print(f"{'chain serialization':28s} median {np.median(ts) * 1e3:7.1f} ms")

    # --- the same workflow at paper scale, simulated ---------------------------
    # one ExperimentSpec, three backends: the numpy backend replays the
    # paper's 30-minute stream in milliseconds; the jax backend compiles a
    # whole (seeds x placements x requests) sweep into one program
    from dataclasses import replace as dc_replace

    from repro.core import simulator as sm

    steps = sm.document_workflow_fig4()
    simspec = sm.ExperimentSpec(steps, n_requests=1800, seeds=(0, 1, 2))
    simulator = sm.WorkflowSimulator(sm.paper_platforms(), seed=0)
    totals = simulator.simulate(simspec, backend="numpy")  # (3, 1800)
    print(
        f"{'simulated (numpy, 3 seeds)':28s} median"
        f" {np.median(totals) * 1e3:7.1f} ms"
    )
    candidates = [
        steps,
        [dc_replace(s, platform="gcf") if s.name == "ocr" else s for s in steps],
    ]
    swept = simulator.simulate_placements(simspec, candidates)  # (3, 2, 1800)
    for cand, label in zip(swept.transpose(1, 0, 2), ("ocr@lambda", "ocr@gcf")):
        print(f"{'  placement ' + label:28s} median {np.median(cand) * 1e3:7.1f} ms")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

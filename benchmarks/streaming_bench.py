"""Streaming data plane bench: chunked, pipelined transfers vs whole-object.

Two parts, mirroring dag_overlap:
  - SIMULATED: the Fig-4 document workflow (and its diamond DAG form) with
    a data-heavy 8 MB payload, chunks=8 vs streaming off, through the
    vectorized backend — the pipelined closed form must cut the p50 by
    >= 20% on the chain and strictly win on the diamond.
  - REAL: a 3-node chain on the actual dataflow engine with enforced store
    latencies and a staging ``payload_region`` (both modes pay the same
    two wire hops; streaming cut-through pipelines them) — the wall-clock
    p50 must also drop >= 20%. A third mode turns on the P2P bypass for
    the same payload to show the direct path under the threshold.

Output: CSV-ish ``name,median_s`` rows (written to
``experiments/bench/BENCH_streaming.json`` by the runner).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import Platform, PlatformRegistry, StreamConfig
from repro.core.simulator import ExperimentSpec, WorkflowSimulator
from repro.core.simulator import document_workflow_fig4, paper_platforms
from repro.dag import (
    DagDeployment,
    DagSpec,
    DagStep,
    DagWorkflowSimulator,
    document_dag_fig4,
)

PAYLOAD_BYTES = 8e6
CHUNKS = 8


def run_sim(n: int = 2000) -> dict:
    rows = {}
    for label, stream in [("off", None), ("stream", StreamConfig(chunks=CHUNKS))]:
        sim = WorkflowSimulator(
            paper_platforms(),
            seed=42,
            payload_size_bytes=PAYLOAD_BYTES,
            stream=stream,
        )
        out = sim.simulate(
            ExperimentSpec(document_workflow_fig4(), n_requests=n),
            backend="numpy",
        )
        rows[f"sim_chain_{label}"] = float(np.median(out))
    steps, edges = document_dag_fig4()
    for label, stream in [("off", None), ("stream", StreamConfig(chunks=CHUNKS))]:
        sim = DagWorkflowSimulator(
            paper_platforms(),
            seed=42,
            payload_size_bytes=PAYLOAD_BYTES,
            stream=stream,
        )
        out = sim.simulate(
            ExperimentSpec(steps, edges=edges, n_requests=n), backend="numpy"
        )
        rows[f"sim_dag_{label}"] = float(np.median(out))
    return rows


def _make_engine(stream=None):
    reg = PlatformRegistry()
    reg.register(Platform("edge-eu", "eu", kind="edge", native_prefetch=True))
    reg.register(Platform("cloud-us", "us", kind="cloud"))
    # staging region "mid": payload buffers home there for BOTH modes, so
    # each buffered edge pays two real wire hops — the comparison is fair
    # and the streamed cut-through has an actual pipeline to collapse
    dep = DagDeployment(reg, stream=stream, payload_region="mid")
    dep.store.enforce_latency = True
    dep.store.network.set_link("eu", "us", 0.04, 8e6)
    dep.store.network.set_link("eu", "mid", 0.03, 8e6)
    dep.store.network.set_link("mid", "us", 0.03, 8e6)

    def handler(s):
        def h(payload, data):
            time.sleep(s)
            return payload

        return h

    dep.deploy("a", handler(0.02), ["edge-eu"])
    dep.deploy("b", handler(0.25), ["cloud-us"])
    dep.deploy("c", handler(0.02), ["cloud-us"])
    return dep


ENGINE_SPEC = DagSpec(
    (DagStep("a", "edge-eu"), DagStep("b", "cloud-us"), DagStep("c", "cloud-us")),
    (("a", "b"), ("b", "c")),
    "stream-chain",
)


def run_real(runs: int = 5) -> dict:
    payload = np.zeros(int(2e6 // 8))  # 2 MB on the wire per edge
    rows = {}
    modes = [
        ("off", None),
        ("stream", StreamConfig(chunks=CHUNKS)),
        ("p2p", StreamConfig(chunks=CHUNKS, p2p_threshold_bytes=4e6)),
    ]
    for label, stream in modes:
        with _make_engine(stream) as dep:
            dep.run(ENGINE_SPEC, payload)  # warm pools
            ts = [dep.run(ENGINE_SPEC, payload).total_s for _ in range(runs)]
            rows[f"real_chain_{label}"] = float(np.median(ts))
            if label == "stream":
                assert dep.stats["streamed_edges"] > 0, dep.stats
            if label == "p2p":
                assert dep.stats["p2p_edges"] > 0, dep.stats
    return rows


def main(quick: bool = False) -> dict:
    rows = run_sim(n=400 if quick else 2000)
    rows.update(run_real(runs=3 if quick else 7))
    print("name,median_s")
    for name, value in rows.items():
        print(f"{name},{value:.4f}")
    sim_win = 1.0 - rows["sim_chain_stream"] / rows["sim_chain_off"]
    real_win = 1.0 - rows["real_chain_stream"] / rows["real_chain_off"]
    print(f"derived,sim_p50_reduction,{sim_win:.3f}")
    print(f"derived,real_p50_reduction,{real_win:.3f}")
    # acceptance: pipelining beats whole-object by >= 20% p50 in the sim
    # AND on the real engine; the diamond DAG must improve too
    assert sim_win >= 0.20, rows
    assert real_win >= 0.20, rows
    assert rows["sim_dag_stream"] < rows["sim_dag_off"], rows
    assert rows["real_chain_p2p"] < rows["real_chain_off"], rows
    return rows


if __name__ == "__main__":
    main()

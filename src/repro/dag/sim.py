"""DAG simulation facade — the recurrence lives in the unified simulator.

``repro.core.simulator.WorkflowSimulator`` executes one dataflow recurrence
for chains and DAGs (``payload[v] = max over preds of end[u] + transfer``),
mirroring the runtime where the chain deployer is a facade over the
dataflow engine. This module keeps the historical DAG-side names importable
(``DagWorkflowSimulator`` IS the unified simulator) and hosts the
calibrated DAG shapes used by the chain-vs-DAG experiments.
"""

from __future__ import annotations

from repro.core.simulator import (  # noqa: F401
    DagTrace,
    Dist,
    SimStep,
    WorkflowSimulator,
    serialize_chain,
)

# A degenerate subclass kept for its established name: every capability —
# run_request AND run_dag_request — already lives on the unified simulator.
DagWorkflowSimulator = WorkflowSimulator


# ---------------------------------------------------------------------------
# calibrated DAG shapes (the paper's §4.2 workflow, restructured)
# ---------------------------------------------------------------------------
def document_dag_fig4():
    """The Fig-4 document workflow as a real fan-out: after ``check``, the
    virus scan and the OCR don't depend on each other — run them in
    parallel and join at ``e_mail``. Same calibrated distributions as
    ``simulator.document_workflow_fig4`` so the chain serialization of
    these steps IS the paper's chain."""
    steps = [
        SimStep("check", "tinyfaas-edge", compute=Dist(0.22)),
        SimStep("virus", "gcf", compute=Dist(0.30), fetch=Dist(0.32)),
        SimStep("ocr", "lambda-us-east-1", compute=Dist(0.45), fetch=Dist(1.45)),
        SimStep("e_mail", "lambda-us-east-1", compute=Dist(0.20), fetch=Dist(0.85)),
    ]
    edges = [
        ("check", "virus"),
        ("check", "ocr"),
        ("virus", "e_mail"),
        ("ocr", "e_mail"),
    ]
    return steps, edges


GENOME_POPULATIONS = ("AFR", "AMR", "EAS", "EUR", "GBR", "SAS", "ALL")


def genome_dag_1chr(chunks: int = 48):
    """The 1000Genome workflow of one chromosome
    (github.com/pegasus-isi/1000genome-workflow): ``chunks`` parallel
    ``individuals`` steps fan in to ``individuals_merge``; ``sifting`` is a
    second source; per population a ``mutation_overlap`` and a
    ``frequency`` sink each read both the merge and ``sifting``. At 48
    chunks: 64 steps, 76 edges, 3 topological levels, a 48-way join.
    Steps are listed in a topological order; the costs are set, in the
    proportions of the workflow's task types, not calibrated."""
    individuals = [f"individuals_{i:02d}" for i in range(chunks)]
    data = "lambda-eu-central-1"
    steps = [
        SimStep(n, data, compute=Dist(3.2, 0.25), fetch=Dist(0.8, 0.3))
        for n in individuals
    ]
    steps += [
        SimStep("sifting", data, compute=Dist(1.1, 0.15), fetch=Dist(0.6, 0.3)),
        SimStep("individuals_merge", data, compute=Dist(2.4, 0.15),
                fetch=Dist(0.3, 0.3)),
    ]
    edges = [(n, "individuals_merge") for n in individuals]
    for kind, compute in (("mutation_overlap", 1.6), ("frequency", 3.0)):
        for pop in GENOME_POPULATIONS:
            sink = f"{kind}_{pop}"
            steps.append(
                SimStep(sink, "gcf", compute=Dist(compute, 0.2), fetch=Dist(0.3, 0.3))
            )
            edges += [("individuals_merge", sink), ("sifting", sink)]
    return steps, edges

"""repro.dag — THE dataflow execution core (chains are degenerate DAGs).

Every workflow in this repo executes here; the chain stack
(``repro.core.choreographer``) is a facade that lifts ``WorkflowSpec``
through ``DagSpec.from_chain``:

  spec     DagSpec / DagStep — per-request DAG routing (JSON round-trip,
           topological validation, from_chain lift, place_dag wiring)
  engine   DagDeployment — the one dataflow executor: pokes cascade along
           edges, nodes fire when their last predecessor payload lands,
           branches run concurrently on the platform executors, poke
           timing learns per (pred -> succ) edge
  sim      DagWorkflowSimulator — alias of the unified simulator
           (core.simulator), which runs one recurrence for chains + DAGs
"""

from repro.dag.spec import DagSpec, DagStep, place_dag_spec  # noqa: F401
from repro.dag.engine import DagDeployment, DagResult, DeployedFn  # noqa: F401
from repro.dag.sim import (  # noqa: F401
    DagTrace,
    DagWorkflowSimulator,
    document_dag_fig4,
    genome_dag_1chr,
    serialize_chain,
)

"""Benchmark entry point: one bench per paper table/figure + system benches.

  paper_figs        Figs 4/6/8 medians + CDFs (vectorized simulator,
                    multi-seed error bars)
  vecsim            vectorized vs scalar simulation core (asserts >= 20x
                    speedup and <= 1% median/p99 gaps)
  jaxsim            jax backend vs numpy backend on the scorer-shaped
                    (seeds x placements x requests) sweep (asserts >= 5x
                    on the full sweep and <= 1% median/p99 gaps)
  dag_overlap       chain vs DAG medians, +-prefetch (sim + real engine)
  placement         exact place_dag DP vs greedy baseline (asserts DP wins)
  adapt             online recomposition vs static under 5x mid-run drift
                    (sim + real engine; asserts >= 25% recovery, <= 2%
                    no-drift overhead)
  slo               burn-rate alerting closes the loop: cost triggers off,
                    the obs SLO tracker alone forces the re-placement
                    (sim + real engine + what-if profiler direction check)
  faults            durability under injected outages: the outage trigger
                    holds availability >= 99% (sim) / >= 95% (real engine)
                    while static placements lose the whole window; dead
                    letters + retry span events on the report surfaces
  wrapper_overhead  §4.1 wrapper < 1 ms (real wall-clock)
  real_overlap      real-JAX latency hiding on this host (not simulated)
  pipeline_overlap  data-pipeline DoubleBuffer vs sync input
  streaming         chunked pipelined data plane vs whole-object transfers
                    (sim + real engine; asserts >= 20% p50 reduction on
                    both, plus the P2P bypass beating the buffered path)
  timing            §5.5 eager vs learned poke timing (beyond-paper)
  roofline          per-cell three-term table from the dry-run artifacts
  trace_diff        sim-vs-real critical-path diff on the traced document
                    workflow (repro.obs; writes a Perfetto JSON sample)

Output: CSV-ish ``name,us_per_call,derived`` blocks per bench, plus one
machine-readable ``experiments/bench/BENCH_<name>.json`` per bench (the
bench's returned rows + wall time) so the perf trajectory is tracked
across commits instead of scrolling away in CI logs.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback
from datetime import datetime, timezone

BENCH_OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")


def _git_sha() -> str:
    """Current commit SHA, or "unknown" outside a repo / without git."""
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "HEAD"],
                capture_output=True,
                text=True,
                timeout=5,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            .stdout.strip()
            or "unknown"
        )
    except Exception:
        return "unknown"


def _jax_backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def _write_bench_json(name: str, wall_s: float, rows, quick: bool = False) -> None:
    """One JSON artifact per bench: rows (when the bench returned a dict)
    + wall time, stamped with the commit SHA, UTC timestamp and run flags
    so artifacts from different commits can be told apart.
    Non-serializable values degrade to strings rather than failing the
    bench."""
    os.makedirs(BENCH_OUT, exist_ok=True)
    payload = {
        "bench": name,
        "wall_s": round(wall_s, 4),
        "rows": rows if isinstance(rows, dict) else None,
        "git_sha": _git_sha(),
        "utc": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "quick": quick,
        "jax_backend": _jax_backend(),
    }
    path = os.path.join(BENCH_OUT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True, default=str)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="reduced sample counts — the CI smoke gate that "
        "keeps the perf scripts importable and running",
    )
    args = ap.parse_args(argv)

    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)  # `benchmarks` as a package from anywhere
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        adapt_bench,
        dag_overlap,
        faults_bench,
        jaxsim_bench,
        paper_figs,
        pipeline_overlap,
        placement_bench,
        real_overlap,
        roofline,
        slo_bench,
        streaming_bench,
        timing_bench,
        vecsim_bench,
        wrapper_overhead,
    )

    # the simulated benches ride the vectorized path now, so the full run
    # uses paper-scale x ~28 (50k requests) instead of the scalar 1800
    n_fig = 80 if args.quick else 50_000
    seeds_fig = (42, 43) if args.quick else (42, 43, 44, 45, 46)
    benches = [
        (
            "paper_figs",
            lambda: paper_figs.main(n=n_fig, write=not args.quick, seeds=seeds_fig),
        ),
        ("vecsim", vecsim_bench.main),
        ("jaxsim", lambda: jaxsim_bench.main(quick=args.quick)),
        (
            "dag_overlap",
            lambda: dag_overlap.main(
                n=max(n_fig, 1800), runs_real=3 if args.quick else 7
            ),
        ),
        ("placement", placement_bench.main),
        (
            "adapt",
            lambda: adapt_bench.main(
                n=160 if args.quick else 1200, runs_real=40 if args.quick else 64
            ),
        ),
        ("slo", lambda: slo_bench.main(quick=args.quick)),
        (
            "faults",
            lambda: faults_bench.main(
                n=240 if args.quick else 400, runs_real=48 if args.quick else 64
            ),
        ),
        (
            "wrapper_overhead",
            lambda: wrapper_overhead.main(n_calls=100 if args.quick else 2000),
        ),
        ("real_overlap", real_overlap.main),
        (
            "pipeline_overlap",
            lambda: pipeline_overlap.main(steps=4 if args.quick else 8),
        ),
        ("streaming", lambda: streaming_bench.main(quick=args.quick)),
        ("timing", timing_bench.main),
        ("roofline", roofline.main),
    ]

    # sim-vs-real critical-path diff (repro.obs): a script, not a package
    # module — import it off the scripts dir like a bench
    sys.path.insert(0, os.path.join(root, "scripts"))
    import trace_diff

    benches.append(("trace_diff", lambda: trace_diff.main(quick=args.quick)))
    failed = []
    for name, fn in benches:
        print(f"\n===== bench: {name} =====")
        try:
            t0 = time.perf_counter()
            rows = fn()
            _write_bench_json(name, time.perf_counter() - t0, rows, quick=args.quick)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"\nFAILED benches: {failed}")
        sys.exit(1)
    print("\nall benches OK")


if __name__ == "__main__":
    main()

from bench.sweep_reference import scorer_totals, sweep_totals, transfer_s  # noqa: F401

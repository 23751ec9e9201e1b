"""The benchmark of this repository: one command runs one cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names the cells; each
cell's configuration, traffic mix and per-layer metrics are files under
``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by name.
"""

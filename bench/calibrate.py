#!/usr/bin/env python3
"""Readings that set a cell's rate and its correctness limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... --seconds <s>
        [--control <k>] [--rates r1,r2,... [--rates-seconds <s>]]
        [--fault <name>]

One process, one set-up: ``--rates`` first runs a window at each offered
rate (a served cell's knee sweep: the highest rate whose backlog does not
grow over the window); then, for each seed, a window at the mix's rate and
the numbers the correctness comparison reads, and for the first ``k``
seeds the same numbers read from the control (the plain reference in the
precision below the configuration's, in the program's place). With
``--fault`` a fault of ``bench/faults.py`` is planted in the program before
set-up, and the readings are the faulty program's. One JSON object per line
on standard output. The limits in ``bench/limits`` are set
from these readings: above the largest sound reading, below the smallest
control reading.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def knee_stats(result, seconds):
    """Backlog over a window: latency of the first and last third of the
    requests, and how long after the window's close the last one ended."""
    import numpy as np

    recs = result["records"]
    lat = np.array([r["latency_s"] for r in recs])
    third = max(1, len(lat) // 3)
    ends = [r["due"] + r["latency_s"] for r in recs]
    t0 = recs[0]["due"]
    return {
        "n": len(lat),
        "failed": result["failed"],
        "p50_s": float(np.median(lat)),
        "p90_s": float(np.quantile(lat, 0.9)),
        "first_third_p50_s": float(np.median(lat[:third])),
        "last_third_p50_s": float(np.median(lat[-third:])),
        "drain_s": float(max(ends) - t0 - seconds),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--rates-seconds", type=float, default=0.0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)

    from bench import faults, harness

    if args.fault:
        faults.plant(args.fault)

    cell = harness.Cell(harness.load_spec(), args.workload)
    harness.device_info(cell.chips)
    harness.configure_jax()
    sysm = harness.system(cell.config["system"]).System(
        cell.name, cell.config, cell.mix, int(args.seeds.split(",")[0]),
        harness.reference(cell.config_name), harness.log,
    )
    phases = {}
    sysm.setup(phases)
    setup_s = time.perf_counter() - T_START
    print(json.dumps({"setup": phases, "setup_s": setup_s}), flush=True)
    base_mix = sysm.mix
    for rate in [float(r) for r in args.rates.split(",") if r]:
        sysm.mix = dict(base_mix, rate_per_s=rate)
        secs = args.rates_seconds or args.seconds
        res = sysm.window(secs, None)
        row = {"rate_per_s": rate} | knee_stats(res, secs)
        print(json.dumps(row), flush=True)
    sysm.mix = base_mix
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        sysm.reseed(seed)
        res = sysm.window(args.seconds, None)
        row = {"seed": seed, "fault": args.fault, "attempted": res["attempted"], "failed": res["failed"]}
        row |= sysm.end_to_end(res)
        row["program"] = sysm.readings(res)
        if i < args.control:
            row["control"] = sysm.readings(res, control=True)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()

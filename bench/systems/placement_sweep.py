"""GeoFF's control plane: the compiled placement sweep over a workflow.

Two loops, both closed (each call waits for the last):

- ``scorer``: the recomposition controller's decision. Each call builds a
  ``PlacementScorer(backend="jax")``, prices the mix's candidate
  placements with ``distributions`` and takes each placement's quantile in
  host memory. The cost model is redrawn per decision from the seed (the
  telemetry drift the controller sees); the placement set, and so every
  shape, stays fixed.
- ``simulate_placements``: capacity planning and what-if replays; large
  sweeps of the workflow on the configuration's platforms, back to back,
  each result copied to the host.

The window closes with the first call that ends at or after ``--seconds``,
so a rate counts all the work and all the time of the window.
"""

from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from bench import traffic
from bench.harness import load_json, span

SAMPLE_CALLS = {"scorer": 8, "simulate_placements": 1}  # calls the reference checks
WARMUP_CALL = 1 << 40  # draws of the warm-up calls, apart from the window's


def placements(cfg: dict, mix: dict) -> list:
    """The candidate placements of the mix, as lists of platform names in
    workflow order."""
    steps = [s["name"] for s in cfg["workflow"]]
    base = [s["platform"] for s in cfg["workflow"]]
    plats = [p["name"] for p in cfg["platforms"]]
    kind, count = mix["placements"]["kind"], mix["placements"]["count"]
    if kind == "product":
        # fixed steps stay put, the others range over every platform
        fixed = mix["placements"]["fixed_steps"]
        free = [i for i, s in enumerate(steps) if s not in fixed]
        out = []
        for combo in itertools.product(plats, repeat=len(free)):
            pl = list(base)
            for i, p in zip(free, combo):
                pl[i] = p
            out.append(pl)
    elif kind == "rotate_middle":
        # rotate the platform of one middle step through the platform set
        out = []
        for i in range(count):
            pl = list(base)
            pl[1 + i % (len(steps) - 2)] = plats[i % len(plats)]
            out.append(pl)
    else:
        raise ValueError(f"unknown placement kind {kind!r}")
    if len(out) < count:
        raise ValueError(f"{kind} gives {len(out)} placements, the mix asks {count}")
    return out[:count]


class System:
    def __init__(self, cell, cfg, mix, seed, ref, log):
        if mix["loop"] != "closed" or mix["call"] not in SAMPLE_CALLS:
            raise ValueError(f"cannot drive {mix['loop']} loop of {mix['call']!r}")
        self.cell, self.cfg, self.mix, self.seed = cell, cfg, mix, seed
        self.ref, self.log = ref, log
        self.traced = False

    # -- set-up -----------------------------------------------------------------
    def setup(self, phases):
        from repro.adapt import PlacementScorer
        from repro.core import PlacementCosts
        from repro.core import simulator as S

        t = time.perf_counter()
        cfg, mix = self.cfg, self.mix
        self.S, self.PlacementScorer = S, PlacementScorer
        self.PlacementCosts = PlacementCosts
        self.cands = placements(cfg, mix)
        self.dtype = np.dtype(mix["dtype"]).type
        if mix["call"] == "simulate_placements":
            ol = cfg["object_latency"]
            self.sim = S.WorkflowSimulator(
                [
                    S.SimPlatform(
                        p["name"],
                        p["region"],
                        native_prefetch=p["native_prefetch"],
                        allows_sync=p["allows_sync"],
                        cold_start=S.Dist(*p["cold_start"]),
                        keep_warm_s=p["keep_warm_s"],
                    )
                    for p in cfg["platforms"]
                ],
                msg_latency_s=cfg["msg_latency_s"],
                object_latency=S.ObjectLatency(**ol),
                payload_size_bytes=cfg["payload_size_bytes"],
            )
            self.step_sets = [
                [
                    S.SimStep(s["name"], plat, compute=S.Dist(*s["compute"]),
                              fetch=S.Dist(*s["fetch"]))
                    for s, plat in zip(cfg["workflow"], pl)
                ]
                for pl in self.cands
            ]
        phases["build_s"] = time.perf_counter() - t
        # two calls: the first compiles or loads the sweep, the second shows
        # that nothing is left to compile
        for i, name in enumerate(("warmup_first_s", "warmup_second_s")):
            t = time.perf_counter()
            self._call(WARMUP_CALL + i)
            phases[name] = time.perf_counter() - t

    def _scorer_inputs(self, call: int):
        """The cost model of one decision, from the configuration: each
        step's compute and fetch medians times a lognormal drift factor per
        (step, platform) drawn from the seed, and the configuration's
        object-store edges between platforms."""
        cfg = self.cfg
        steps = [s["name"] for s in cfg["workflow"]]
        plats = {p["name"]: p for p in cfg["platforms"]}
        names = list(plats)
        drift = traffic.drift_factors(cfg, self.seed, call, len(names))
        comp = {s["name"]: s["compute"][0] for s in cfg["workflow"]}
        fetch = {s["name"]: s["fetch"][0] for s in cfg["workflow"]}

        def compute_s(n, p):
            return comp[n] * float(drift[0, steps.index(n), names.index(p)])

        def fetch_s(n, p, deps):
            return fetch[n] * float(drift[1, steps.index(n), names.index(p)])

        def transfer_s(a, b, size):
            return self.ref.transfer_s(cfg, plats[a], plats[b])

        costs = self.PlacementCosts(
            fetch_s=fetch_s, compute_s=compute_s, transfer_s=transfer_s,
            payload_size=cfg["payload_size_bytes"],
        )
        seeds = traffic.sweep_seeds(self.seed, call, self.mix["sweep_seeds"])
        return costs, seeds, drift

    def _call(self, call: int):
        """One decision or one sweep; returns what it hands the host."""
        mix = self.mix
        if mix["call"] == "scorer":
            costs, seeds, _ = self._scorer_inputs(call)
            sc = mix["scorer"]
            scorer = self.PlacementScorer(
                n_requests=mix["n_requests"],
                quantile=sc["quantile"],
                sigma=sc["sigma"],
                interarrival_s=self.cfg["interarrival_s"],
                msg_latency_s=self.cfg["msg_latency_s"],
                backend="jax",
                seeds=seeds,
            )
            steps = [s["name"] for s in self.cfg["workflow"]]
            nodes = {n: None for n in steps}
            edges = list(zip(steps, steps[1:]))
            cands = [dict(zip(steps, pl)) for pl in self.cands]
            dists = scorer.distributions(nodes, edges, cands, costs)
            with span("bench.quantiles", self.traced):
                q = np.quantile(dists, sc["quantile"], axis=1)
            return dists, q
        seeds = traffic.sweep_seeds(self.seed, call, mix["sweep_seeds"])
        spec = self.S.ExperimentSpec(
            self.step_sets[0], n_requests=mix["n_requests"],
            interarrival_s=self.cfg["interarrival_s"], prefetch=self.cfg["prefetch"],
            seeds=seeds,
        )
        out = self.sim.simulate_placements(spec, self.step_sets, dtype=self.dtype)
        return out, None

    # -- the window -----------------------------------------------------------------
    def window(self, seconds, prof):
        self.traced = prof is not None
        self.outputs, self.calls = [], []
        if prof is not None:
            prof.start()
        t0 = time.perf_counter()
        call = 0
        while True:
            t = time.perf_counter()
            name = "bench.decision" if self.mix["call"] == "scorer" else "bench.sweep"
            with span(name, self.traced):
                out = self._call(call)
            t_end = time.perf_counter()
            self.outputs.append(out)
            self.calls.append((t, t_end))
            call += 1
            if t_end - t0 >= seconds:
                break
        self.t_window = (t0, t_end)
        return {"attempted": call, "failed": 0}

    def lateness_lines(self):
        c = np.array(self.calls)
        gaps = (c[1:, 0] - c[:-1, 1]) * 1e3 if len(c) > 1 else np.zeros(1)
        return [
            f"closed loop, {len(c)} calls: host gap between calls ms median "
            f"{np.median(gaps):.4f} max {gaps.max():.4f}"
        ]

    def end_to_end(self, result):
        from bench.harness import quantile_ms

        c = np.array(self.calls)
        if self.mix["call"] == "scorer":
            return {"decision_p95_ms": quantile_ms(c[:, 1] - c[:, 0], 0.95)}
        n = sum(out.size for out, _ in self.outputs)
        return {"sim_requests_per_s": n / (self.t_window[1] - self.t_window[0])}

    def trace_records(self, t_trace):
        return {"n_requests": self.mix["n_requests"],
                "rows": self.mix["sweep_seeds"] * len(self.cands)}

    # -- after the window ---------------------------------------------------------
    def release(self):
        gc.collect()

    def reseed(self, seed):
        self.seed = seed

    def readings(self, result, control=False):
        """The numbers the comparison reads over sampled calls of the
        window: every total against the plain reference, in units of the
        float32 spacing at the request's absolute end time (the sweep adds
        up absolute times in float32, so that spacing is its rounding
        step, and an error late in a long stream weighs as much as an
        early one), and (scorer) every placement's quantile. With
        ``control``, the reference in bfloat16 takes the program's place."""
        import ml_dtypes

        n_calls = len(self.outputs)
        pick = traffic.sample(n_calls, SAMPLE_CALLS[self.mix["call"]], self.seed,
                              must=[n_calls - 1])
        gaps, quants = [], []
        t = time.perf_counter()
        mix, cfg = self.mix, self.cfg
        n = mix["n_requests"]
        t0s = np.arange(n) * cfg["interarrival_s"]
        for call in pick:
            got, q = self.outputs[call]
            if mix["call"] == "scorer":
                _, seeds, drift = self._scorer_inputs(call)
                want = self.ref.scorer_totals(cfg, mix, self.cands, drift, seeds)
                if control:
                    got = self.ref.scorer_totals(cfg, mix, self.cands, drift, seeds,
                                                 dtype=ml_dtypes.bfloat16)
                    got = got.astype(np.float64)
                    q = np.quantile(got, mix["scorer"]["quantile"], axis=1)
                want_q = np.quantile(want, mix["scorer"]["quantile"], axis=1)
                quants.append(np.max(np.abs(q - want_q) / want_q))
            else:
                seeds = traffic.sweep_seeds(self.seed, call, mix["sweep_seeds"])
                want = self.ref.sweep_totals(cfg, mix, self.cands, seeds)
                if control:
                    got = self.ref.sweep_totals(cfg, mix, self.cands, seeds,
                                                dtype=ml_dtypes.bfloat16)
            shape = (-1, n)  # one stream of requests per row
            gaps.append(ulp_gap(np.reshape(got, shape), np.reshape(want, shape), t0s))
        gap = np.concatenate(gaps)
        eighths = [float(np.max(g)) for g in np.array_split(gap, 8, axis=1)]
        self.log(f"reference{' control' if control else ''}: {len(pick)} calls in "
                 f"{time.perf_counter() - t:.1f} s; end_time_ulp_gap by eighth of "
                 f"the stream: {eighths}")
        out = {"end_time_ulp_gap": float(np.max(gap))}
        if mix["call"] == "scorer":
            out["quantile_rel_gap"] = float(np.max(quants))
        return out

    def check(self, result, control=False):
        limits = load_json("limits", f"{self.cell}.json")
        readings = self.readings(result, control)
        return {k: {"value": v, "limit": limits[k]} for k, v in readings.items()}


def ulp_gap(got, want, t0s):
    """|program - reference| of each total, in units of the float32 spacing
    at the request's absolute end time (arrival plus the reference's
    total); a NaN stays a NaN."""
    end = (np.asarray(t0s, np.float64) + want).astype(np.float32)
    unit = np.spacing(np.abs(end)).astype(np.float64)
    return np.abs(np.asarray(got, np.float64) - want) / unit

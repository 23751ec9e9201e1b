"""Run one cell of the benchmark once and print its result line.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``bench/configs/<config>.json`` with the plain reference
beside it (``<config>.reference.py``), its traffic mix in
``bench/traffic/<mix>.json``, the system that drives the program in
``bench/systems/<system>.py`` (``system`` is a key of the configuration),
and each per-layer metric's reader in ``bench/metrics/<metric>.py``, or,
for a metric ``<quantity>.<mix>``, in the one that mixes share,
``bench/metrics/<quantity>.py``.

A run: set-up (imports, weights or data, compiles, warm-up: ``setup_s``),
one measured window of ``--seconds``, the peak device memory, then the
program's state is freed and the plain reference checks what the window
produced, and the window is held to having compiled nothing. With
``--trace 1`` the window is the same, the engine carries an
``obs`` tracer, and the profiler records a stretch of it; the per-layer
metrics are read from that, the end-to-end metrics are not reported.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import re
import sys
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# JAX's persistent compilation cache and the profiler's scratch: fixed
# paths inside the checkout (listed in .gitignore), so that a second run
# of a cell in the same checkout finds every program the first compiled
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
TRACE_SECONDS = 8.0  # longest stretch of a window the profiler records
TRACE_SETTLE = 1.0  # seconds between starting the profiler and its window
DRAIN_SECONDS = 60.0  # how long past the window's close answers are awaited


def log(*args):
    print(*args, file=sys.stderr, flush=True)


# -- finding things by name ------------------------------------------------------
def load_spec(root=ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> dict:
    return load_json("configs", f"{name}.json")


def reference(name: str):
    path = os.path.join(BENCH, "configs", f"{name}.reference.py")
    return load_module(path, "bench_reference_" + re.sub(r"\W", "_", name))


def mix(name: str) -> dict:
    return load_json("traffic", f"{name}.json")


def system(name: str):
    path = os.path.join(BENCH, "systems", f"{name}.py")
    return load_module(path, f"bench_system_{name}")


def reader(metric: str):
    """The reader of ``metric``: ``metrics/<metric>.py``, else, for a metric
    named ``<quantity>.<mix>``, the reader every mix shares,
    ``metrics/<quantity>.py``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH, "metrics", f"{metric.split('.')[0]}.py")
    return load_module(path, "bench_metric_" + re.sub(r"\W", "_", metric))


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, spec: dict, name: str, config_=None, mix_=None):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}"
            )
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.mix_name = self.entry["traffic"]
        self.config = config_ or config(self.config_name)
        self.mix = mix_ or mix(self.mix_name)

    def end_to_end(self) -> list:
        return [
            m
            for m in self.spec["end_to_end"]
            if self.name in m.get("workloads", [self.name])
        ]

    def per_layer(self) -> list:
        mine = {m["name"] for m in self.end_to_end()}
        return [
            m
            for m in self.spec["per_layer"]
            if (self.name in m["workloads"] if "workloads" in m else m["moves"] in mine)
        ]


# -- the run ---------------------------------------------------------------------
def configure_jax():
    """Before the first compile: the checkout's own persistent cache, for
    every program whatever its size or compile time."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    """The device as JAX reports it; exits (no result) unless JAX holds at
    least ``chips`` TPU chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {d.platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    armed: the measured window must have none."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)
    HITS = ("/jax/compilation_cache/cache_hits",)

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _bump(self):
        if self.armed:
            with self._lock:
                self.count += 1

    def _on_duration(self, event, duration, **kw):
        if event in self.EVENTS:
            self._bump()

    def _on_event(self, event, **kw):
        if event in self.HITS:
            self._bump()


class Profiler:
    """Records ``seconds`` of a window with the JAX profiler, bracketed by a
    ``bench.window`` host span, from a thread of its own."""

    def __init__(self, seconds: float, delay: float):
        self.seconds, self.delay = seconds, delay
        self.thread = None
        self.t = (None, None)

    def start(self):
        self.thread = threading.Thread(target=self._run, name="bench-profiler")
        self.thread.start()

    def _run(self):
        import jax

        time.sleep(self.delay)
        os.makedirs(TRACE_DIR, exist_ok=True)
        jax.profiler.start_trace(TRACE_DIR)
        # starting the profiler stalls the process for a moment: the
        # recorded window opens after that
        time.sleep(TRACE_SETTLE)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(self.seconds)
        self.t = (t0, time.perf_counter())
        jax.profiler.stop_trace()

    def read(self):
        """Join the thread, read the trace and delete it from disk."""
        import glob
        import shutil

        from bench import xtrace

        self.thread.join()
        pattern = os.path.join(TRACE_DIR, "**", "*.xplane.pb")
        files = sorted(glob.glob(pattern, recursive=True))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        try:
            planes = xtrace.load(files[-1])
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return planes


def peak_memory(device) -> int:
    """Peak bytes of one chip: buffers in use plus the runtime's reserved
    region, which holds the programs' temporaries (a compiled program's
    scratch never shows in ``peak_bytes_in_use``)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0)
    )


def quantile_ms(samples_s, q: float) -> float:
    """Exact quantile (linear between order statistics) of raw samples in
    seconds, returned in ms; a failed request is an infinite sample."""
    import numpy as np

    x = np.sort(np.asarray(samples_s, float))
    if not len(x):
        return math.inf
    finite = np.where(np.isfinite(x), x, 1e300)
    v = float(np.quantile(finite, q))
    return math.inf if v >= 1e299 else v * 1e3


def finite(value):
    """JSON has no infinity or NaN: such a reading prints as the largest
    float."""
    return value if value is None or math.isfinite(value) else sys.float_info.max


def metric(value, unit):
    return {"value": finite(value), "unit": unit}


@dataclass
class Context:
    """What a per-layer reader reads: the traced window on each chip, the
    benchmark's host spans in it, the peaks, and the system's own records."""

    cell: object
    devices: list
    spans: list
    peaks: dict
    records: dict


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict, out=print, control=False) -> dict:
    """One run of ``cell``; prints and returns the result line's object.
    With ``control`` the comparison reads the control (the reference in
    the precision below the configuration's) in the program's place: the
    benchmark's own runs never do."""
    import jax

    phases = {"import_s": time.perf_counter() - t_start}
    mod = system(cell.config["system"])
    sysm = mod.System(
        cell.name, cell.config, cell.mix, seed, reference(cell.config_name), log
    )
    sysm.setup(phases)
    setup_s = time.perf_counter() - t_start
    log("setup: " + json.dumps({k: round(v, 4) for k, v in phases.items()}
                               | {"setup_s": setup_s}))

    counter = CompileCounter()
    prof = None
    if trace:
        prof = Profiler(min(TRACE_SECONDS, seconds * 0.6), seconds * 0.1)
    counter.armed = True
    result = sysm.window(seconds, prof)
    counter.armed = False
    log(f"window: compiles or cache loads inside it: {counter.count}")
    for line in sysm.lateness_lines():
        log(line)

    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory: {json.dumps(stats)}")
    peak_bytes = max(peak_memory(d) for d in jax.devices()[: cell.chips])
    device = dict(device, memory_peak_bytes=peak_bytes)

    line = {
        "correct": None,
        "attempted": result["attempted"],
        "failed": result["failed"],
    }
    if trace:
        from bench import xtrace

        planes = prof.read()
        lo, hi = xtrace.window(planes, "bench.window")
        devs = xtrace.device_traces(planes, lo, hi)[: cell.chips]
        if not devs:
            raise RuntimeError("the trace holds no device plane")
        spans = [s for s in xtrace.host_spans(planes)
                 if s.name != "bench.window" and s.end_ns > lo and s.start_ns < hi]
        peaks = xtrace.peaks_for(device["kind"])
        records = sysm.trace_records(prof.t)
        ctx = Context(cell, devs, spans, peaks, records)
        metrics = {}
        for m in cell.per_layer():
            v = reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = metric(v, m["unit"])
        busy = sum(d.busy_s() for d in devs) / len(devs)
        device.update(busy_s=busy, window_s=devs[0].window_s)
        kernels = sorted({xtrace.instr_name(e.name) for d in devs for e, _ in d.ops
                          if xtrace.is_kernel(e.name)})
        log(f"trace: {len(planes)} planes, kernels seen: {kernels[:20]}")
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = {
            "device_ops": xtrace.top_ops(devs[0]),
            "idle_gaps": xtrace.idle_gaps(devs[0], spans),
        }
    else:
        values = sysm.end_to_end(result) | {"setup_s": setup_s}
        line["metrics"] = {m["name"]: metric(values[m["name"]], m["unit"])
                           for m in cell.end_to_end()}
        line["device"] = device

    sysm.release()
    checks = sysm.check(result, control) | {
        # nothing may compile or load a program inside the measured window
        "window_compiles": {"value": counter.count, "limit": 0},
    }
    line["correct"] = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values()
    )
    line["checks"] = {
        k: {"value": finite(c["value"]), "limit": c["limit"]} for k, c in checks.items()
    }
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    out(json.dumps(line), flush=True)
    return line


def main(args, t_start: float) -> int:
    spec = load_spec()
    cell = Cell(spec, args.workload)
    device = device_info(cell.chips)
    configure_jax()
    run(cell, args.seed, float(args.seconds), bool(args.trace), t_start, device)
    return 0


@contextmanager
def span(name: str, on: bool):
    """A host span on the profiler's clock when ``on``; nothing otherwise."""
    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield

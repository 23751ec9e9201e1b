"""Reduce a profiler trace to device busy time, kernel time and roofline
shares.

The JAX profiler writes an ``.xplane.pb``; ``load`` reads it with
``jax.profiler.ProfileData`` into plain ``Plane``/``Line``/``Event``
records, so every function below also runs on traces built by hand in the
tests. On a TPU the device planes are named ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per executed HLO instruction (the event
name is the instruction's text, ``%name = shape op(...)``) and their
``XLA Modules`` line one event per executed program (``jit_<fn>(<id>)``).
Host planes carry the benchmark's own ``jax.profiler.TraceAnnotation``
spans, whose names start with ``bench.``, and the program's spans
(``repro.obs.span``), whose names start with ``geoff.``.

Device and host timestamps share one time base, up to a skew of about a
millisecond; host spans are used only to label idle gaps and to bound the
traced window.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
BENCH_SPANS = ("bench.",)  # the benchmark's own spans, which readers time
HOST_SPANS = ("bench.", "geoff.")  # and the program's, which name idle gaps


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)

    def line(self, name):
        return next((ln for ln in self.lines if ln.name == name), None)


def load(path) -> list:
    """The planes of one ``.xplane.pb`` as plain records."""
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        lines = [
            Line(ln.name, [Event(e.name, e.start_ns, e.duration_ns) for e in ln.events])
            for ln in p.lines
        ]
        planes.append(Plane(p.name, lines))
    return planes


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip, from ``peaks.json``. A chip that is
    not in the table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; bench/peaks.json has "
            f"{sorted(table)}"
        )
    return table[device_kind]


def device_planes(planes) -> list:
    return [p for p in planes if p.name.startswith("/device:") and p.line(OPS_LINE)]


def host_spans(planes, prefixes=BENCH_SPANS) -> list:
    """The annotations whose names start with one of ``prefixes``, from
    every host thread, by start."""
    return sorted(
        (
            e
            for p in planes
            if p.name.startswith("/host:")
            for ln in p.lines
            for e in ln.events
            if e.name.startswith(prefixes)
        ),
        key=lambda e: e.start_ns,
    )


def busy_intervals(events, lo=float("-inf"), hi=float("inf")) -> list:
    """The union of the events' intervals as sorted disjoint (start, end)."""
    out = []
    for s, e in sorted((max(ev.start_ns, lo), min(ev.end_ns, hi)) for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def union_ns(events, lo=float("-inf"), hi=float("inf")) -> float:
    """Length of the union of the events' intervals clipped to [lo, hi]:
    overlapping intervals count once."""
    return sum(e - s for s, e in busy_intervals(events, lo, hi))


def instr_name(op_text: str) -> str:
    """``%flash_attention.3 = bf16[...] custom-call(...)`` -> ``flash_attention.3``."""
    head = op_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def base_name(instr: str) -> str:
    """Instruction name without XLA's ``.<n>`` suffix."""
    return re.sub(r"(\.\d+)+$", "", instr)


def module_name(module_event_name: str) -> str:
    """``jit_serve_decode_2112(1234)`` -> ``jit_serve_decode_2112``."""
    return module_event_name.split("(", 1)[0]


def is_kernel(op_text: str) -> bool:
    """A Pallas kernel compiled for the TPU is a ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in op_text


_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def shapes(op_text: str) -> list:
    """Every ``dtype[dims]`` in an op's text, output first then operands:
    [(dtype, (dims...)), ...]."""
    return [
        (dt, tuple(int(d) for d in dims.split(",") if d))
        for dt, dims in _SHAPE.findall(op_text.split(", custom_call_target")[0])
    ]


@dataclass
class DeviceTrace:
    """One chip's ops and programs inside a window, with the programs' names
    attached to the ops they contain."""

    ops: list  # [(Event, module_name)]
    modules: list  # [Event]
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> float:
        return union_ns([e for e, _ in self.ops], self.lo, self.hi) * 1e-9

    def module_events(self, prefix: str) -> list:
        return [m for m in self.modules if module_name(m.name).startswith(prefix)]

    def kernel_events(self, kernel: str) -> list:
        return [
            (e, mod)
            for e, mod in self.ops
            if is_kernel(e.name) and base_name(instr_name(e.name)) == kernel
        ]


def window(planes, span_name: str) -> tuple:
    """[lo, hi] in ns of the host span that brackets the traced window."""
    spans = [s for s in host_spans(planes) if s.name == span_name]
    if not spans:
        raise ValueError(f"trace holds no {span_name!r} span")
    return spans[0].start_ns, spans[0].end_ns


def device_traces(planes, lo: float, hi: float) -> list:
    """Per chip: its ops and programs that overlap [lo, hi]."""
    out = []
    for p in device_planes(planes):
        mods_line = p.line(MODULES_LINE)
        modules = sorted(
            (m for m in (mods_line.events if mods_line else []) if m.end_ns > lo
             and m.start_ns < hi),
            key=lambda m: m.start_ns,
        )
        ops = []
        starts = [m.start_ns for m in modules]
        for e in p.line(OPS_LINE).events:
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = (
                module_name(modules[i].name)
                if i >= 0 and modules[i].end_ns >= e.start_ns
                else ""
            )
            ops.append((e, mod))
        out.append(DeviceTrace(ops, modules, lo, hi))
    return out


def roofline_share(work, kernel_s: float, peaks: dict):
    """Share of the roofline, in percent, of kernel calls that did ``work``
    (a list of (flops, bytes) per call) in ``kernel_s`` device seconds: the
    least time the chip could take, the larger of flops over peak FLOP/s and
    bytes over HBM bandwidth summed per call, over the time taken. Returns
    (percent, bound) with bound "compute" or "memory" (the bound of most of
    the least time), or None when there is no kernel time to read."""
    if kernel_s <= 0 or not work:
        return None
    t_compute = t_memory = 0.0
    for flops, nbytes in work:
        tc = flops / peaks["bf16_flops_per_s"]
        tm = nbytes / peaks["hbm_bytes_per_s"]
        if tc >= tm:
            t_compute += tc
        else:
            t_memory += tm
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * (t_compute + t_memory) / kernel_s, bound


_CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def is_container(op_text: str) -> bool:
    """Control flow (a loop, a branch, a call) whose event spans the ops it
    runs."""
    return bool(_CONTAINER.search(op_text.split(" = ", 1)[-1]))


def top_ops(dev: DeviceTrace, k: int = 10) -> list:
    """The device ops that took most time, by program and instruction name,
    control flow left out (its ops count on their own): [[name, seconds],
    ...]."""
    tot: dict = {}
    for e, mod in dev.ops:
        if is_container(e.name):
            continue
        key = f"{mod}/{instr_name(e.name)}"[:160]
        tot[key] = tot.get(key, 0.0) + e.dur_ns * 1e-9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(dev: DeviceTrace, spans, k: int = 10) -> list:
    """The longest idle gaps of the device inside the window, each labelled
    by what the host did for most of it: every instant of the gap goes to
    the innermost span that covers it (the shortest, where spans nest), or
    to ``host`` where none does, and the gap takes the name that holds the
    most of its time: [[label, seconds], ...], longest first. ``spans``,
    sorted by start, excludes the span that brackets the window."""
    busy = busy_intervals([e for e, _ in dev.ops], dev.lo, dev.hi)
    edges = [dev.lo] + [x for iv in busy for x in iv] + [dev.hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    gaps = sorted((g for g in gaps if g[1] > g[0]), key=lambda g: g[0] - g[1])[:k]
    out = []
    for g0, g1 in gaps:
        inside = []
        for s in spans:
            if s.start_ns >= g1:
                break
            if s.end_ns > g0:
                inside.append(s)
        cuts = sorted({g0, g1} | {t for s in inside for t in (s.start_ns, s.end_ns)
                                  if g0 < t < g1})
        held: dict = {}
        cover, j = [], 0  # the spans that cover the piece [a, b]
        for a, b in zip(cuts, cuts[1:]):
            while j < len(inside) and inside[j].start_ns <= a:
                cover.append(inside[j])
                j += 1
            cover = [s for s in cover if s.end_ns > a]
            name = min(cover, key=lambda s: s.dur_ns).name if cover else "host"
            held[name] = held.get(name, 0.0) + (b - a)
        out.append([max(held, key=held.get), (g1 - g0) * 1e-9])
    return out


def module_mean_ms(dev: DeviceTrace, prefix: str):
    """Mean device duration, in ms, of the executions inside the window of
    the programs whose names start with ``prefix``; None when none ran."""
    runs = [
        m
        for m in dev.module_events(prefix)
        if m.start_ns >= dev.lo and m.end_ns <= dev.hi
    ]
    return sum(m.dur_ns for m in runs) * 1e-6 / len(runs) if runs else None

"""The reduction from a profiler trace to device busy time, kernel time and
roofline shares, on traces built by hand in the shape a TPU writes."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import xtrace  # noqa: E402
from bench.harness import Context, reader  # noqa: E402
from bench.xtrace import Event, Line, Plane  # noqa: E402

FLASH = (
    "%flash_attention.1 = bf16[1,16,256,128]{3,2,1,0} custom-call("
    "bf16[1,16,256,128]{3,2,1,0} %q, bf16[1,8,256,128]{3,2,1,0} %k, "
    'bf16[1,8,256,128]{3,2,1,0} %v), custom_call_target="tpu_custom_call"'
)
COLD = (
    "%cold_scan.3 = s32[512,128]{1,0} custom-call(s32[512,128]{1,0} %pad), "
    'custom_call_target="tpu_custom_call"'
)
PEAKS = xtrace.peaks_for("TPU v5 lite")


def plane(ops, modules, spans=()):
    """A device plane and a host plane with the benchmark's spans."""
    device = [Line("XLA Modules", list(modules)), Line("XLA Ops", list(ops))]
    host = [Line("python", [Event("bench.window", 0, 1000)] + list(spans))]
    return [Plane("/device:TPU:0", device), Plane("/host:CPU", host)]


def op(name, start, dur):
    return Event(f"%{name} = f32[8] fusion()", start, dur)


def test_overlapping_intervals_count_once():
    evs = [Event("a", 0, 100), Event("b", 50, 100), Event("c", 300, 50),
           Event("d", 310, 10)]
    assert xtrace.union_ns(evs) == 200
    assert xtrace.union_ns(evs, lo=60, hi=320) == 90 + 20
    assert xtrace.busy_intervals(evs) == [(0, 150), (300, 350)]


def test_busy_and_idle_share_of_a_window():
    planes = plane(
        [op("fusion.1", 100, 200), op("fusion.2", 250, 100), op("fusion.3", 900, 300)],
        [Event("jit_serve_decode_320(1)", 90, 300),
         Event("jit_serve_decode_320(2)", 890, 320)],
    )
    lo, hi = xtrace.window(planes, "bench.window")
    (dev,) = xtrace.device_traces(planes, lo, hi)
    assert dev.window_s == pytest.approx(1e-6)
    assert dev.busy_s() == pytest.approx((250 + 100) * 1e-9)
    ctx = Context(None, [dev], [], PEAKS, {})
    assert reader("device_idle.serve").read(ctx) == pytest.approx(65.0)
    # a program cut by the window's edge is not a whole execution
    assert xtrace.module_mean_ms(dev, "jit_serve_decode_") == pytest.approx(300e-6)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        xtrace.peaks_for("TPU v99")


def test_kernel_events_by_name_and_shapes_of_the_call():
    planes = plane([Event(FLASH, 10, 100), Event(COLD, 200, 50)], [])
    (dev,) = xtrace.device_traces(planes, 0, 1000)
    calls = dev.kernel_events("flash_attention")
    assert [xtrace.instr_name(e.name) for e, _ in calls] == ["flash_attention.1"]
    assert len(dev.kernel_events("cold_scan")) == 1
    out, _, k, _ = xtrace.shapes(FLASH)
    assert out == ("bf16", (1, 16, 256, 128)) and k == ("bf16", (1, 8, 256, 128))


def test_roofline_share_names_its_bound_and_stays_under_100():
    # a call that needs 1 ms of compute at peak and runs in 2 ms is at 50%
    flops = PEAKS["bf16_flops_per_s"] * 1e-3
    share, bound = xtrace.roofline_share([(flops, 1.0)], 2e-3, PEAKS)
    assert share == pytest.approx(50.0) and bound == "compute"
    nbytes = PEAKS["hbm_bytes_per_s"] * 1e-3
    share, bound = xtrace.roofline_share([(1.0, nbytes)] * 2, 4e-3, PEAKS)
    assert share == pytest.approx(50.0) and bound == "memory"
    assert xtrace.roofline_share([], 1.0, PEAKS) is None
    assert xtrace.roofline_share([(1.0, 1.0)], 0.0, PEAKS) is None


def test_flash_attention_reader_uses_the_call_shapes():
    from bench import flops

    prefill = Event("jit_serve_prefill_256(7)", 0, 200_000)
    planes = plane([Event(FLASH, 10, 100_000)], [prefill])
    (dev,) = xtrace.device_traces(planes, 0, 1e6)
    ctx = Context(None, [dev], [], PEAKS, {})
    f, b = flops.flash_attention_work((1, 16, 256, 128), (1, 8, 256, 128))
    want = 100 * max(f / PEAKS["bf16_flops_per_s"], b / PEAKS["hbm_bytes_per_s"]) / 1e-4
    assert reader("flash_attention_roofline.serve").read(ctx) == pytest.approx(want)
    # no kernel in the window: the reader returns nothing, never 0
    empty = Context(None, xtrace.device_traces(plane([], []), 0, 1e6), [], PEAKS, {})
    assert reader("flash_attention_roofline.serve").read(empty) is None


def test_cold_scan_reader_counts_unpadded_bytes():
    planes = plane([Event(COLD, 0, 1000), Event(COLD, 2000, 1000)], [])
    (dev,) = xtrace.device_traces(planes, 0, 1e6)
    ctx = Context(None, [dev], [], PEAKS, {"rows": 8, "n_requests": 512})
    want = 100 * 2 * (8 * 8 * 512 / PEAKS["hbm_bytes_per_s"]) / 2e-6
    assert reader("cold_scan_roofline.decide").read(ctx) == pytest.approx(want)


def test_idle_gaps_take_the_innermost_host_span():
    spans = [Event("bench.decision", 0, 1000), Event("bench.quantiles", 495, 120)]
    planes = plane([op("a", 0, 500), op("b", 610, 390)], [], spans)
    (dev,) = xtrace.device_traces(planes, 0, 1000)
    gaps = xtrace.idle_gaps(dev, spans)
    assert gaps == [["bench.quantiles", pytest.approx(110e-9)]]


def test_scorer_host_time_pairs_sweeps_with_decisions():
    spans = [Event("bench.decision", 100, 400), Event("bench.decision", 600, 300)]
    modules = [Event("jit__sweep(1)", 300, 100), Event("jit__sweep(2)", 800, 50)]
    planes = plane([Event("%f = f32[] add()", 300, 100)], modules, spans)
    (dev,) = xtrace.device_traces(planes, 0, 1000)
    ctx = Context(None, [dev], spans, PEAKS, {})
    got = reader("scorer_host_ms.decide").read(ctx)
    assert got == pytest.approx(((400 - 100) + (300 - 50)) / 2 * 1e-6)
    assert reader("sweep_device_ms.decide").read(ctx) == pytest.approx(75e-6)


def test_top_ops_are_named_by_program_and_instruction():
    planes = plane(
        [op("fusion.1", 10, 30), op("fusion.1", 50, 30),
         Event("%copy.2 = f32[8] copy()", 90, 5),
         Event("%while.3 = (s32[]) while((s32[]) %t), body=%b", 5, 90)],
        [Event("jit_serve_decode_320(1)", 0, 100)],
    )
    (dev,) = xtrace.device_traces(planes, 0, 1000)
    top = xtrace.top_ops(dev)
    assert top[0] == ["jit_serve_decode_320/fusion.1", pytest.approx(60e-9)]
    assert top[1][0] == "jit_serve_decode_320/copy.2"

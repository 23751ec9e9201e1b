"""The reader of the cold-scan kernel's lane use, on traces built by hand
in the shape a TPU writes: one sweep's rows x nodes over the lanes of the
kernel calls inside one whole ``jit__sweep``, each call's lanes read from
its code plane's shape."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import xtrace  # noqa: E402
from bench.harness import Context, reader  # noqa: E402
from bench.xtrace import Event, Line, Plane  # noqa: E402

PEAKS = xtrace.peaks_for("TPU v5 lite")
RECORDS = {"rows": 8, "n_requests": 131072, "nodes": 64, "edges": 76}


def cold(lanes, start):
    text = (
        f"%cold_scan.3 = s32[131072,{lanes}]{{1,0}} custom-call("
        f"s32[131072,{lanes}]{{1,0}} %pad), "
        'custom_call_target="tpu_custom_call"'
    )
    return Event(text, start, 100)


def read(calls_per_sweep, sweeps=2, lo=0, hi=1e6):
    """``sweeps`` executions of ``jit__sweep``, each making the calls of
    the given lane widths, read in the window [lo, hi]."""
    modules, ops = [], []
    for i in range(sweeps):
        t0 = 100_000 * i
        modules.append(Event(f"jit__sweep({i})", t0, 90_000))
        ops += [cold(lanes, t0 + 1000 * (c + 1))
                for c, lanes in enumerate(calls_per_sweep)]
    device = [Line("XLA Modules", modules), Line("XLA Ops", ops)]
    planes = [Plane("/device:TPU:0", device)]
    (dev,) = xtrace.device_traces(planes, lo, hi)
    ctx = Context(None, [dev], [], PEAKS, RECORDS)
    return reader("cold_scan_lane_use.replay").read(ctx)


def test_lane_use_of_one_call_per_level():
    """The 1000Genome replay's 8 rows x 64 nodes in three calls of 512,
    128 and 128 lanes: two thirds of the lanes carry a row."""
    assert read([512, 128, 128]) == pytest.approx(200 / 3)


def test_lane_use_of_one_call_per_node():
    """One 128-lane call per node holds 8 rows: 6.25%."""
    assert read([128] * 64) == pytest.approx(6.25)


@pytest.mark.parametrize(
    "calls,sweeps,hi",
    [([512, 128, 128], 0, 1e6), ([], 2, 1e6), ([512, 128, 128], 1, 50_000)],
    ids=["no sweep", "no kernel call", "no whole sweep"],
)
def test_lane_use_reads_nothing_without_a_whole_sweep_and_its_calls(calls, sweeps, hi):
    assert read(calls, sweeps=sweeps, hi=hi) is None


def test_lane_use_reads_nothing_from_a_call_without_an_operand_shape():
    bare = Event('%cold_scan.3 = custom-call(), custom_call_target="tpu_custom_call"',
                 1000, 100)
    device = [Line("XLA Modules", [Event("jit__sweep(0)", 0, 90_000)]),
              Line("XLA Ops", [bare, cold(128, 2000)])]
    (dev,) = xtrace.device_traces([Plane("/device:TPU:0", device)], 0, 1e6)
    ctx = Context(None, [dev], [], PEAKS, RECORDS)
    assert reader("cold_scan_lane_use.replay").read(ctx) is None

"""The trace reduction, on planes built by hand (exact arithmetic) and on
a small trace recorded on one TPU v5e chip by ``record_trace.py``."""

import pathlib
from types import SimpleNamespace as NS

import pytest

import xplane

RECORDED = pathlib.Path(__file__).with_name("small.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, end_ns=start + dur)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 9000),
        ev("bench.event", 1000, 3000),
        ev("bench.frame", 5000, 3000)])])
    chip = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_kernel(3)", 2000, 2500),
                                       ev("jit_kernel(4)", 6000, 500)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.3 = f32[16]{0} fusion(f32[16]{0} %p)", 2000, 1000),
            ev("%fusion = f32[4]{0} fusion(f32[4]{0} %q)", 2500, 1000),
            ev("%copy-done.1 = f32[3] copy-done(%c)", 6000, 500),
            ev("%while.20 = (s32[]) while(%t)", 9500, 2000)])])
    other = NS(name="/device:TPU:0 SparseCore", lines=[])
    return [host, chip, other]


def test_busy_is_the_union_of_ops_inside_the_window():
    s = xplane.summarize(planes())
    assert s.chips == 1
    assert s.window_s == pytest.approx(9e-6)
    # [2000, 3500) + [6000, 6500) + [9500, 10000) clipped to the window
    assert s.busy_s == pytest.approx(2.5e-6)
    assert s.idle_pct == pytest.approx(100 * (1 - 2.5 / 9))
    assert s.ops_s == pytest.approx({"fusion": 2e-6, "copy-done": 0.5e-6,
                                     "while": 0.5e-6})
    assert s.modules_n == {"jit_kernel": 2}
    assert s.modules_s["jit_kernel"] == pytest.approx(3e-6)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    s = xplane.summarize(planes())
    # gaps: [1000,2000) event, [3500,6000) midpoint 4750 -> no span,
    # [6500,9500) frame
    assert s.gaps_s == pytest.approx({"bench.event": 1e-6,
                                      xplane.UNNAMED_GAP: 2.5e-6,
                                      "bench.frame": 3e-6})
    b = xplane.breakdown(s)
    assert b["device_ops"][0] == ["fusion", pytest.approx(2e-6)]
    assert b["idle_gaps"][0] == ["bench.frame", pytest.approx(3e-6)]


def test_a_complete_trace_has_a_launch_in_every_request():
    s = xplane.summarize(planes())
    assert (s.requests, s.requests_dark, s.launches_dark) == (1, 0, 0)
    assert s.complete


@pytest.mark.parametrize("drop, dark", [
    ("XLA Modules", (1, 0)),   # the frame's launch was lost
    ("XLA Ops", (0, 2)),       # every launch lost its operations
])
def test_a_trace_that_lost_events_is_incomplete(drop, dark):
    host, chip, other = planes()
    chip.lines = [ln if ln.name != drop else NS(name=drop, events=[])
                  for ln in chip.lines]
    s = xplane.summarize([host, chip, other])
    assert (s.requests_dark, s.launches_dark) == dark
    assert not s.complete


def test_a_trace_without_a_chip_is_refused():
    with pytest.raises(ValueError):
        xplane.summarize(planes()[:1])


def test_recorded_chip_trace():
    from jax.profiler import ProfileData
    s = xplane.summarize(ProfileData.from_file(str(RECORDED)).planes)
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    # one traffic-app frame: its operators, the slices that cut it into
    # parts and the concatenations that gather them, each its own program
    assert s.modules_n["jit__op_pi"] == 4
    assert s.modules_n["jit_dynamic_slice"] > s.modules_n["jit_concatenate"]
    assert "fusion" in s.ops_s
    assert s.gaps_s["bench.frame"] > s.gaps_s[xplane.UNNAMED_GAP]
    assert s.requests == 1 and s.complete

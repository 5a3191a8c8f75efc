"""Reduce a profiler trace (``.xplane.pb``) to device busy time, program and
operation times, and idle gaps attributed to the benchmark's host spans.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation that ran and the ``XLA Modules`` line one event per
program launch.  Busy time is the union of the operation intervals inside
the window.  The window is the host annotation ``bench.window``, so host
spans and device events are read on the trace's one clock.  An idle gap is
named by the benchmark span (``bench.frame``, ``bench.cosim``,
``bench.search``) that covers its midpoint on the host,
or ``host.wait`` where none does: the host was waiting for the next due
item.

A trace that lost launches is told apart from an idle device: every
request span (``bench.frame``, ``bench.cosim``, ``bench.search``) blocks on
device work, so one that no program launch overlaps on any chip means the
profiler dropped events, as does a launch that no operation overlaps; the
summary is then marked incomplete.
"""

from __future__ import annotations

import bisect
import dataclasses
import itertools
import pathlib
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNNAMED_GAP = "host.wait"
REQUESTS = ("bench.frame", "bench.cosim", "bench.search")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float                          # mean over chips
    ops_s: Dict[str, float]                # op kind -> seconds, all chips
    modules_s: Dict[str, float]            # program -> seconds, all chips
    modules_n: Dict[str, int]              # program -> launches, all chips
    gaps_s: Dict[str, float]               # host span -> idle s, chip mean
    requests: int = 0                      # request spans in the window
    requests_dark: int = 0                 # of them, with no launch traced
    launches_dark: int = 0                 # launches with no operation

    @property
    def complete(self) -> bool:
        """Every request in the window has a program launch in the trace,
        and every launch its operations."""
        return self.requests_dark == 0 and self.launches_dark == 0

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def program_name(module: str) -> str:
    """A launch's program name without the launch id (``jit_f(12)``)."""
    return re.sub(r"\(\d+\)$", "", module)


def op_name(op: str) -> str:
    """An operation's kind from its HLO text (``%fusion.12 = f32[..]
    fusion(..)`` reads ``fusion``)."""
    return re.sub(r"[.\d]+$", "", op.split(" = ")[0].lstrip("%"))


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def summarize(planes) -> TraceSummary:
    """Reduce the planes of one trace (``ProfileData.planes``)."""
    host_spans: List[Tuple[int, int, str]] = []
    window: Optional[Tuple[int, int]] = None
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (int(ev.start_ns), int(ev.end_ns))
                elif ev.name.startswith("bench."):
                    host_spans.append((int(ev.start_ns), int(ev.end_ns),
                                       ev.name))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    w0, w1 = window
    host_spans.sort()
    starts = [lo for lo, _, _ in host_spans]
    ops: Dict[str, float] = defaultdict(float)
    mods: Dict[str, float] = defaultdict(float)
    mods_n: Dict[str, int] = defaultdict(int)
    gaps: Dict[str, float] = defaultdict(float)
    launches: List[Tuple[int, int]] = []
    launches_dark = 0
    busy_total = 0.0
    for plane in devices:
        intervals, mod_iv = [], []
        for line in plane.lines:
            for ev in line.events:
                lo, hi = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
                if hi <= lo:
                    continue
                if line.name == OPS_LINE:
                    intervals.append((lo, hi))
                    ops[op_name(ev.name)] += (hi - lo) * 1e-9
                elif line.name == MODULES_LINE:
                    mods[program_name(ev.name)] += (hi - lo) * 1e-9
                    mods_n[program_name(ev.name)] += 1
                    mod_iv.append((lo, hi))
        launches += mod_iv
        launches_dark += _dark(mod_iv, intervals)
        busy = _union(intervals)
        busy_total += sum(hi - lo for lo, hi in busy) * 1e-9
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                name = _covering(host_spans, starts, (lo + hi) // 2)
                gaps[name] += (hi - lo) * 1e-9
    requests = [(lo, hi) for lo, hi, name in host_spans
                if name in REQUESTS and w0 <= lo and hi <= w1]
    n = len(devices)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, chips=n, busy_s=busy_total / n,
        ops_s=dict(ops), modules_s=dict(mods), modules_n=dict(mods_n),
        gaps_s={k: v / n for k, v in gaps.items()},
        requests=len(requests), requests_dark=_dark(requests, launches),
        launches_dark=launches_dark)


def _dark(spans: List[Tuple[int, int]],
          launches: List[Tuple[int, int]]) -> int:
    """How many of ``spans`` no launch interval overlaps."""
    launches = sorted(launches)
    starts = [lo for lo, _ in launches]
    reach = list(itertools.accumulate((hi for _, hi in launches), max))
    dark = 0
    for lo, hi in spans:
        i = bisect.bisect_left(starts, hi) - 1
        dark += int(i < 0 or reach[i] <= lo)
    return dark


def _covering(spans: List[Tuple[int, int, str]], starts: List[int],
              t: int) -> str:
    """The benchmark span (they never overlap) that holds host time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return UNNAMED_GAP


def read(log_dir) -> TraceSummary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(find_xplane(log_dir))).planes)


def breakdown(summary: TraceSummary) -> Dict[str, list]:
    """The ten operations that took most device time and the ten host
    spans under which the device idled longest, in seconds."""
    top = sorted(summary.ops_s.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(summary.gaps_s.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}

"""What every load of ``bench/loads/`` shares: the numbers that decide
``correct``, the timed requests, and the comparison of simulated surfaces.

A load builds the system under test from its configuration file, warms
up every shape its traffic will use, drives the timed window, and after
the window compares what the window produced with the plain references.
Each load lives in ``bench/loads/<name>.py`` and names its class ``LOAD``;
a traffic file picks it by ``"load"``.  Nothing here knows a cell by name.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np
from jax.profiler import TraceAnnotation

import generator
from reference import ticks as ref_ticks

#: the control's precision: one below what the configuration states
LOWER = {"operators": ml_dtypes.bfloat16, "cosimulation": np.float32}

#: called between requests with the host clock, so that the harness can
#: close a traced window at a request boundary
Between = Callable[[float], None]


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit: correct while
    ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Item:
    """One timed request: its due time (None when back to back), start and
    end on the host clock, and what it did."""

    due: Optional[float]
    start: float
    end: float
    kind: str
    ok: bool = True
    cells: float = 0.0


class Load:
    """What every load provides to the harness."""

    def __init__(self, cfg: Mapping, traffic: Mapping, seed: int, devices,
                 limits: Mapping[str, float]):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.devices = list(devices)
        self.limits = limits
        self.items: List[Item] = []
        self.window: Tuple[float, float] = (0.0, 0.0)

    def setup(self, seconds: float) -> None:
        raise NotImplementedError

    def run(self, seconds: float, between: Optional[Between] = None) -> None:
        raise NotImplementedError

    def release(self) -> None:
        """Drop the program's state before the reference runs."""

    def check(self, control: bool = False) -> List[Check]:
        """The numbers that decide ``correct``.  With ``control`` the
        reference computed one precision below what the configuration
        states (``LOWER``) stands in for the program: a sound limit fails
        it.  The benchmark's own runs never ask for the control."""
        raise NotImplementedError

    def _check(self, name: str, value: float) -> Check:
        return Check(name, float(value), float(self.limits[name]))


class BackToBack(Load):
    """Requests one after another for the window's length."""

    def _loop(self, seconds: float, request, label: str,
              between: Optional[Between]) -> None:
        self.outputs: List = []
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            start = time.perf_counter()
            with TraceAnnotation(label):
                out = request(n)
            end = time.perf_counter()
            self.items.append(Item(None, start, end, label, True,
                                   self.cells_of(out)))
            self.outputs.append(out)
            n += 1
            if between is not None:
                between(end)
        self.window = (t0, time.perf_counter())

    def cells_of(self, out) -> float:
        raise NotImplementedError

    def _sampled(self) -> list:
        """The outputs compared: ``checked`` of them, drawn from the seed."""
        rng = generator.draws(self.seed, "sample")
        k = min(int(self.traffic["checked"]), len(self.outputs))
        return [self.outputs[i] for i in
                sorted(rng.choice(len(self.outputs), k, replace=False))]

    def _simulate(self, facts, dtype) -> List[ref_ticks.DagResult]:
        return ref_ticks.simulate(
            facts, self.cfg["profiles"], duration=self.opts["duration"],
            dt=self.opts["dt"], warmup=self.opts["warmup"],
            sample_every=self.opts["latency_sample_every"], dtype=dtype)


def surfaces(r: ref_ticks.DagResult) -> Dict[str, np.ndarray]:
    return {"latency_samples": r.latency_samples,
            "latency_slope": r.latency_slope, "stable": r.stable,
            "queue_total": r.queue_total, "slot_busy": r.slot_busy}


def surface_gap(got: Sequence[Mapping], want: Sequence) -> Tuple[float, int]:
    """``(err, flips)``: the widest gap of any surface, as a share of the
    largest magnitude of that surface over every DAG or candidate compared,
    and the number of stability verdicts that differ."""
    gaps: Dict[str, float] = {}
    scale: Dict[str, float] = {}
    flips = 0
    for g, w in zip(got, want):
        for field in ("latency_samples", "latency_slope", "queue_total",
                      "slot_busy"):
            if field not in g:
                continue
            a, b = g[field], getattr(w, field)
            if field == "slot_busy":
                if set(a) != set(b):
                    gaps[field] = np.inf
                    continue
                a = np.array([a[k] for k in sorted(b)])
                b = np.array([b[k] for k in sorted(b)])
            a, b = np.asarray(a, dtype=np.float64), np.asarray(b, np.float64)
            if a.shape != b.shape:
                gaps[field] = np.inf
                continue
            if b.size:
                gaps[field] = max(gaps.get(field, 0.0),
                                  float(np.max(np.abs(a - b))))
                scale[field] = max(scale.get(field, 0.0),
                                   float(np.max(np.abs(b))))
        flips += int(np.count_nonzero(np.asarray(g["stable"])
                                      != np.asarray(w.stable)))
    err = max((gaps[f] / max(scale.get(f, 0.0), 1e-300) for f in gaps),
              default=0.0)
    return err, flips

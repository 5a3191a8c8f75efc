"""The configuration's keyed, stateful DAG planned at its rate and enacted on
one executor, taking frames of SYS records open loop at that rate: every
frame is due ``frame_tuples / rate`` seconds after the one before, whether
or not the executor has kept up.

Records are made from the seed in set-up: sensor ids uniform over the
configuration's sensors, timestamps in ms at the offered rate, each
observation field uniform over its range.  The keyed kinds keep state
across frames, so the reference (``reference/riot.py``) takes every frame
of the run in order, warm-up frames included, and every frame of the window
is compared: the upload's ``service`` on the circle it wraps on, every
other float field as ``reference/operators.compare`` reads it."""

import dataclasses
import time
from typing import Dict, List, Mapping, Tuple

import ml_dtypes
import numpy as np
from jax.profiler import TraceAnnotation

import base
import deploy
import generator
from reference import operators as ref_ops
from reference import riot

#: frames sent through the executor in set-up: every program of the run
#: compiles in the first, since no shape depends on the data
WARM_FRAMES = 2
#: each observation field's range: temperature (C), humidity (%), light
#: (lux), dust (ug/m3), air quality (raw)
FIELD_RANGES = np.array([[-10.0, 45.0], [0.0, 100.0], [0.0, 2000.0],
                         [0.0, 500.0], [0.0, 1000.0]], np.float32)
@dataclasses.dataclass
class Frame(base.Item):
    """A timed frame and its sequence number in the run."""

    seq: int = 0


#: the upload's looked-up field, and the value it wraps at
SERVICE, SERVICE_WRAP = "service", 1000.0


def compare(got: Mapping[str, Mapping[str, np.ndarray]],
            want: Mapping[str, Mapping[str, np.ndarray]]
            ) -> Tuple[int, float, float, Dict[str, float]]:
    """``(mismatches, float_err, service_err, by_field)`` of one frame's
    sinks.  Mismatches and ``float_err`` as ``reference/operators.compare``
    gives them, over every field but ``service``; ``service_err`` the
    widest distance of ``service`` from the reference's on the circle of
    ``SERVICE_WRAP``, since the lookup wraps there and a sum that rounds
    across a multiple of it lands at the far end; ``by_field`` the widest
    error of each field."""
    bad, err, service = 0, 0.0, 0.0
    by_field: Dict[str, float] = {}
    for sink in set(got) | set(want):
        g, w = got.get(sink), want.get(sink)
        if g is None or w is None:
            bad += 1
            continue
        for key in set(g) | set(w):
            if key == SERVICE and key in g and key in w and \
                    np.shape(g[key]) == np.shape(w[key]):
                d = np.abs(np.asarray(g[key], np.float64)
                           - np.asarray(w[key], np.float64)) % SERVICE_WRAP
                e = float(np.max(np.minimum(d, SERVICE_WRAP - d),
                                 initial=0.0))
                service = max(service, e)
            else:
                b, e = ref_ops.compare(
                    {sink: {key: g[key]} if key in g else {}},
                    {sink: {key: w[key]} if key in w else {}})
                bad, err = bad + b, max(err, e)
            by_field[key] = max(by_field.get(key, 0.0), e)
    return bad, err, service, by_field


def sys_frame(seed: int, seq: int, first: int, frame_tuples: int,
              rate: float, p) -> Dict[str, np.ndarray]:
    """Frame ``seq`` of SYS records as a ``(frame_tuples, record_bytes)``
    uint8 payload; ``first`` is the run's index of its first tuple."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(seq), 0x5157])
    n, fields = frame_tuples, p["fields"]
    lo, hi = FIELD_RANGES[:fields, 0], FIELD_RANGES[:fields, 1]
    rec = np.zeros((n, p["record_bytes"]), np.uint8)
    rec[:, 0:4] = rng.integers(0, p["sensors"], n, dtype=np.uint32)[:, None] \
        .view(np.uint8)
    ts = np.round((first + np.arange(n)) * 1000.0 / rate).astype(np.uint32)
    rec[:, 4:8] = ts[:, None].view(np.uint8)
    obs = (lo + (hi - lo) * rng.random((n, fields), dtype=np.float32)) \
        .astype("<f4")
    rec[:, 8:8 + 4 * fields] = obs.view(np.uint8)
    return {"payload": rec}


class KeyedStream(base.Load):

    def setup(self, seconds: float) -> None:
        from repro.core import plan
        from repro.runtime import StreamExecutor, WallClock
        from repro.runtime.operators import KEYED, PARAMETERS
        if self.cfg["parameters"] != PARAMETERS:
            raise ValueError(
                f"the configuration's parameters {self.cfg['parameters']} "
                f"are not the program's {PARAMETERS}")
        lib = deploy.library(self.cfg)
        dag = deploy.dataflow(self.cfg, self.cfg["dag"])
        rate = float(self.cfg["rate"])
        sched = plan(dag, rate, lib, allocator=self.cfg["allocator"],
                     mapper=self.cfg["mapper"],
                     vm_sizes=self.cfg["vm_family"])
        self.executor = StreamExecutor(sched, lib, clock=WallClock(),
                                       devices=self.devices)
        kinds = {t[1] for t in self.cfg["dags"][self.cfg["dag"]]["tasks"]}
        #: the stateful kinds' programs, by the names they carry in a trace
        self.state_programs = sorted(f"jit_{KEYED[k].fn.__name__}"
                                     for k in kinds if k in KEYED)
        tuples = int(self.traffic["frame_tuples"])
        self.due = generator.frame_times(rate, tuples, seconds)
        self.warm = [10 ** 9 + k for k in range(WARM_FRAMES)]
        self.order = [*self.warm, *range(len(self.due))]
        self.payloads = {s: sys_frame(self.seed, s, k * tuples, tuples, rate,
                                      self.cfg["parameters"])
                         for k, s in enumerate(self.order)}
        self.kept: List[Tuple[int, Dict]] = []
        for s in self.warm:
            self._frame(s, time.perf_counter())
        self.items = []

    def _frame(self, seq: int, due: float) -> None:
        from repro.runtime.stream import MicroBatch
        frame = MicroBatch(seq=seq, arrays=self.payloads[seq], created=due)
        start = time.perf_counter()
        with TraceAnnotation("bench.frame"):
            status, _ = self.executor.process_frame(frame, interval=0.0)
        end = time.perf_counter()
        self.items.append(Frame(due, start, end, "frame", status == "ok",
                                seq=seq))
        self.kept.append((seq, dict(self.executor.last_sink_outputs)))

    def run(self, seconds: float, between=None) -> None:
        t0 = time.perf_counter()
        for seq, t in enumerate(self.due):
            due = t0 + t
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self._frame(seq, due)
            if between is not None:
                between(time.perf_counter())
        self.window = (t0, time.perf_counter())

    def release(self) -> None:
        self.kept = [(s, {n: {k: np.asarray(v) for k, v in o.items()}
                          for n, o in sinks.items()})
                     for s, sinks in self.kept]
        self.schedule = self.executor.schedule
        del self.executor

    def check(self, control: bool = False) -> List[base.Check]:
        """Every frame of the window against the reference, which takes the
        run's frames in order; with ``control`` the reference one precision
        lower stands in for the program.  Also keeps the widest error of
        each sink field (``by_field``) and counts the stateful kinds' least
        work per frame (``state_work``)."""
        groups = deploy.mapping_groups(self.schedule.mapping)
        ref = riot.Stats(self.cfg)
        low = riot.Stats(self.cfg, dtype=ml_dtypes.bfloat16) if control \
            else None
        kept = dict(self.kept)
        self.state_work = {}
        bad, err, service = 0, 0.0, 0.0
        self.by_field: Dict[str, float] = {}
        for seq in self.order:
            want = ref.frame(self.payloads[seq], groups)
            self.state_work[seq] = riot.state_work(
                self.cfg["parameters"], ref.valid_in, ref.tasks)
            got = low.frame(self.payloads[seq], groups) if control \
                else kept.get(seq, {})
            if seq in self.warm:
                continue
            b, e, s, fields = compare(got, want)
            bad, err, service = bad + b, max(err, e), max(service, s)
            for k, v in fields.items():
                self.by_field[k] = max(self.by_field.get(k, 0.0), v)
        return [self._check("sink_mismatch", bad),
                self._check("sink_float_err", err),
                self._check("service_err", service)]


LOAD = KeyedStream

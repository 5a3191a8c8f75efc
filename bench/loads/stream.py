"""The configuration's DAG planned at its rate and enacted on one
executor, taking frames open loop at that rate: every frame is due
``frame_tuples / rate`` seconds after the one before, whether or not the
executor has kept up.  Payloads are made in set-up; every frame's sink
outputs are kept for the comparison."""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

import base
import deploy
import generator
from reference import operators as ref_ops

#: frames sent through the executor in set-up, which compiles every part
#: shape the schedule cuts a frame into
WARM_FRAMES = 2


class Stream(base.Load):

    def setup(self, seconds: float) -> None:
        from repro.core import plan
        from repro.runtime import StreamExecutor, WallClock
        lib = deploy.library(self.cfg)
        dag = deploy.dataflow(self.cfg, self.cfg["dag"])
        rate = float(self.cfg["rate"])
        sched = plan(dag, rate, lib, allocator=self.cfg["allocator"],
                     mapper=self.cfg["mapper"],
                     vm_sizes=self.cfg["vm_family"])
        self.executor = StreamExecutor(sched, lib, clock=WallClock(),
                                       devices=self.devices)
        tuples = int(self.traffic["frame_tuples"])
        self.due = generator.frame_times(rate, tuples, seconds)
        self.warm = [10 ** 9 + k for k in range(WARM_FRAMES)]
        self.payloads = {s: generator.frame_payload(
            self.seed, s, tuples, int(self.cfg["payload_bytes"]))
            for s in [*self.warm, *range(len(self.due))]}
        self.kept: List[Tuple[int, Dict]] = []
        for s in self.warm:
            self._frame(s, time.perf_counter())
        self.items, self.kept = [], []

    def _frame(self, seq: int, due: float) -> None:
        from repro.runtime.stream import MicroBatch
        frame = MicroBatch(seq=seq, arrays=self.payloads[seq], created=due)
        start = time.perf_counter()
        with TraceAnnotation("bench.frame"):
            status, _ = self.executor.process_frame(frame, interval=0.0)
        end = time.perf_counter()
        self.items.append(base.Item(due, start, end, "frame", status == "ok"))
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
        """Every frame of the window against the reference; with
        ``control`` the reference one precision lower stands in for the
        program."""
        spec = self.cfg["dags"][self.cfg["dag"]]
        tasks = {t[0]: t[1] for t in spec["tasks"]}
        edges = [(e[0], e[1]) for e in spec["edges"]]
        groups = deploy.mapping_groups(self.schedule.mapping)
        bad, err = 0, 0.0
        for seq, sinks in self.kept:
            want = ref_ops.run_frame(tasks, edges, groups, self.payloads[seq])
            got = (ref_ops.run_frame(tasks, edges, groups, self.payloads[seq],
                                     dtype=base.LOWER["operators"])
                   if control else sinks)
            b, e = ref_ops.compare(got, want)
            bad, err = bad + b, max(err, e)
        return [self._check("sink_mismatch", bad),
                self._check("sink_float_err", err)]


LOAD = Stream

"""Drive the served control loop once on a TPU and check what it returns.

Run from the repository root, in one process (nothing here starts a child
that touches JAX):

    python chip_smoke.py             # phases (a)-(c) on one chip
    python chip_smoke.py --chips 4   # phase (d) only, on a four-chip host

(a) the 20-event bursty-day trace of ``benchmarks/bench_online.py`` (8 DAGs,
    budget 44 growing to 62, two VM failures, a 60-720 tuples/s burst),
    enacted through ``FleetController`` -> ``LiveFleet`` -> ``StreamExecutor``
    on the wall clock with measurement windows on: no frame may fail, lose
    tuples or retry;
(b) the final fleet co-simulated on the jitted scan kernel and on the numpy
    reference: equal within the tests' bound, equal stability verdicts;
(c) ``plan(traffic_dag(), 100, mapper="search")``, then its candidate pool
    evaluated on the vmapped kernel and on numpy: equal within that bound;
(d) one Fig. 8 app DAG schedule enacted on one chip and on four, same
    source seed: bit-identical sink outputs and equal tuple counts.

Each phase prints one line with its seconds, its number of XLA compiles and
its max-abs difference.  The last line is one JSON object naming the device.
A failed check, or a platform other than ``tpu``, exits non-zero before it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

#: scan == numpy and vmap == numpy bound of tests/test_simulator_scan.py and
#: tests/test_search.py (np.allclose with rtol = atol = TOL)
TOL = 1e-10
RAW_FIELDS = ("queues", "busy", "served", "realized", "latency")
#: phase (d): planned rate, frames and source seed of the enactment
D_RATE, D_FRAMES, D_BATCH, D_SEED = 100.0, 24, 16, 0


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Host-clock end time of every XLA backend compile in this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def within(self, windows) -> int:
        return sum(1 for t in self.times
                   if any(t0 <= t <= t1 for t0, t1 in windows))


class Diff:
    """Running max-abs difference of a candidate against a reference, with
    the tests' ``allclose`` bound checked on every pair."""

    def __init__(self, what: str) -> None:
        self.what = what
        self.max_abs = 0.0

    def add(self, got, want, field: str) -> None:
        got, want = np.asarray(got, dtype=float), np.asarray(want, dtype=float)
        check(got.shape == want.shape,
              f"{self.what}: {field} shape {got.shape} != {want.shape}")
        if got.size:
            self.max_abs = max(self.max_abs, float(np.max(np.abs(got - want))))
            check(bool(np.allclose(got, want, rtol=TOL, atol=TOL)),
                  f"{self.what}: {field} differs by "
                  f"{float(np.max(np.abs(got - want)))!r} (bound {TOL})")


def cache_entries(path: pathlib.Path) -> int:
    """Compiled programs in a JAX cache directory: JAX stores each as
    ``<key>-cache`` beside an ``<key>-atime`` stamp."""
    return sum(1 for _ in path.glob("*-cache")) if path.is_dir() else 0


def run_phase(name: str, fn, compiles: CompileLog):
    n0, t0 = len(compiles.times), time.perf_counter()
    out, fields = fn()
    seconds = time.perf_counter() - t0
    fields = {"seconds": seconds, "compiles": len(compiles.times) - n0,
              **fields}
    print(f"phase {name}: " + " ".join(f"{k}={v!r}" for k, v in
                                       fields.items()), flush=True)
    return out


def enacted_fleet(compiles: CompileLog):
    """(a) the bench_online trace, enacted live on the wall clock."""
    from benchmarks.bench_online import (BUDGET0, MAX_RATE, STEP, TRACE,
                                         trace_event)
    from repro.core import FleetController, paper_library
    from repro.obs.trace import Tracer, set_tracer
    from repro.runtime import LiveFleet, WallClock

    kinds = {kind for kind, _ in TRACE}
    check({"arrive", "rate", "grow", "fail", "depart"} <= kinds,
          f"trace lacks an event kind: {sorted(kinds)}")
    lib = paper_library()
    ctl = FleetController(lib, budget_slots=BUDGET0, mapper="sam", step=STEP,
                          max_rate=MAX_RATE)
    fleet = LiveFleet(ctl, clock=WallClock())
    tracer = Tracer(enabled=True)
    previous = set_tracer(tracer)
    try:
        for kind, payload in TRACE:
            fleet.apply(trace_event(ctl, kind, payload))
    finally:
        set_tracer(previous)
    reports = [(i, name, rep) for i, rec in enumerate(fleet.log.records)
               for name, rep in [*rec.reports.items(),
                                 *rec.recovery_reports.items()]]
    check(bool(reports), "no measurement window ran")
    for i, name, rep in reports:
        check(rep.frames_failed == 0 and rep.tuples_lost == 0
              and rep.retries == 0,
              f"event {i} dag {name}: frames_failed={rep.frames_failed} "
              f"tuples_lost={rep.tuples_lost} retries={rep.retries}")
    windows = [(s.t0, s.t1) for s in tracer.spans
               if s.name == "executor.run"]
    check(len(windows) == len(reports), "a measurement window went untraced")
    reps = [rep for _, _, rep in reports]
    return (ctl, lib), {
        "events": len(TRACE), "dags_live": len(ctl.dag_names),
        "windows": len(reps), "frames": sum(r.frames for r in reps),
        "tuples": sum(r.tuples for r in reps),
        "frames_shed": sum(r.frames_shed for r in reps),
        "frames_timed_out": sum(r.frames_timed_out for r in reps),
        "compiles_in_windows": compiles.within(windows)}


def cosimulation(ctl):
    """(b) the final fleet on the scan kernel vs the numpy reference."""
    rep_s = ctl.cosimulate()
    rep_n = ctl.cosimulate(engine="numpy")
    check(rep_s.engine == "scan", f"cosimulate ran engine {rep_s.engine!r}")
    check(rep_s.entries.keys() == rep_n.entries.keys(), "entry sets differ")
    diff = Diff("cosimulate scan vs numpy")
    verdicts = 0
    for name, a in rep_n.entries.items():
        b = rep_s.entries[name]
        check(a.actual_max_stable == b.actual_max_stable,
              f"{name}: max stable {b.actual_max_stable} != "
              f"{a.actual_max_stable}")
        for ra, rb in zip(a.results, b.results):
            check(ra.stable == rb.stable,
                  f"{name}@{ra.omega}: verdict {rb.stable} != {ra.stable}")
            verdicts += 1
            diff.add(rb.latency_slope, ra.latency_slope, "latency_slope")
            diff.add(rb.latency_samples, ra.latency_samples,
                     "latency_samples")
            diff.add(rb.queue_total, ra.queue_total, "queue_total")
    check(rep_s.slot_busy.keys() == rep_n.slot_busy.keys(), "slot sets differ")
    for field in ("slot_busy", "vm_cpu_actual", "vm_mem_actual"):
        want = getattr(rep_n, field)
        got = getattr(rep_s, field)
        diff.add([got[k] for k in want], list(want.values()), field)
    return None, {"dags": len(rep_s.entries), "verdicts": verdicts,
                  "max_abs_diff": diff.max_abs}


def mapper_search(lib):
    """(c) plan(mapper="search"), then vmap vs numpy on its pool."""
    from repro.core import plan, traffic_dag
    from repro.core.search import evaluate_candidates, generate_candidates

    dag = traffic_dag()
    sched = plan(dag, 100, lib, mapper="search")
    cands = generate_candidates(dag, sched.allocation, sched.vms, lib)
    check(sched.search_winner in {c.name for c in cands},
          f"winner {sched.search_winner!r} is not in the regenerated pool")
    maps = [c.mapping for c in cands]
    omegas = 100.0 * np.linspace(0.5, 1.5, 11)
    buckets: list = []
    raw_v = evaluate_candidates(dag, sched.allocation, maps, lib, omegas,
                                engine="vmap", bucket_sizes=buckets)
    raw_n = evaluate_candidates(dag, sched.allocation, maps, lib, omegas,
                                engine="numpy")
    diff = Diff("evaluate_candidates vmap vs numpy")
    for a, b in zip(raw_v, raw_n):
        for f in RAW_FIELDS:
            diff.add(getattr(a, f), getattr(b, f), f)
    return None, {"winner": sched.search_winner, "candidates": len(maps),
                  "buckets": len(buckets), "max_abs_diff": diff.max_abs}


def four_chips():
    """(d) one app DAG schedule on one chip and on four, same seed."""
    import jax

    from repro.core import paper_library, plan, traffic_dag
    from repro.runtime import StreamExecutor, SyntheticSource, VirtualClock

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 found {len(devices)} device(s)")
    lib = paper_library()
    sched = plan(traffic_dag(), D_RATE, lib, allocator="mba", mapper="sam")
    runs = {}
    for label, devs in (("1", devices[:1]), ("4", devices[:4])):
        ex = StreamExecutor(sched, lib, clock=VirtualClock(), devices=devs)
        source = SyntheticSource(D_RATE, batch=D_BATCH, seed=D_SEED,
                                 clock=ex.clock)
        sinks, tuples = [], 0
        for frame in source.frames(n_frames=D_FRAMES):
            # interval 0: no shedding, no watchdog -- every frame runs
            status, _ = ex.process_frame(frame, interval=0.0)
            check(status == "ok", f"{label} chip(s): frame {frame.seq} "
                                  f"ended {status!r}")
            out = {(snk, k): np.asarray(v)
                   for snk, arrays in sorted(ex.last_sink_outputs.items())
                   for k, v in sorted(arrays.items())}
            tuples += sum(v.shape[0] for (_, k), v in out.items()
                          if k == "payload")
            sinks.append(out)
        used = {d.id for d in ex.slot_device.values()}
        runs[label] = (sinks, tuples, used)
    check(len(runs["4"][2]) == 4,
          f"the schedule spans {len(runs['4'][2])} of 4 chips")
    (s1, n1, _), (s4, n4, _) = runs["1"], runs["4"]
    check(n1 == n4 and n1 > 0, f"sink tuples {n1} on 1 chip, {n4} on 4")
    identical = all(
        a.keys() == b.keys() and all(
            a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
            and a[k].tobytes() == b[k].tobytes() for k in a)
        for a, b in zip(s1, s4))
    check(len(s1) == len(s4) and identical,
          "sink outputs differ between 1 and 4 chips")
    return None, {"dag": sched.dag.name, "slots": len(sched.mapping.slots()),
                  "frames": len(s1), "sink_tuples": n1,
                  "bit_identical": identical, "max_abs_diff": 0.0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip enactment (d)")
    args = ap.parse_args(argv)

    import jax

    from repro.jaxenv import init_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    cache = init_compile_cache()
    print(f"compile cache {cache}: {cache_entries(cache)} entries before",
          flush=True)
    compiles = CompileLog()
    try:
        if args.chips == 4:
            run_phase("d_four_chips", four_chips, compiles)
        else:
            ctl, lib = run_phase("a_enacted_fleet",
                                 lambda: enacted_fleet(compiles), compiles)
            run_phase("b_cosimulation", lambda: cosimulation(ctl), compiles)
            run_phase("c_mapper_search", lambda: mapper_search(lib), compiles)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(f"compile cache {cache}: {cache_entries(cache)} entries after",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

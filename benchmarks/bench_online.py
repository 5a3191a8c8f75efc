"""Online controller: incremental replans vs full from-scratch replans.

A 20-event trace (arrivals, departures, rate ramps, VM growth, VM
failures) drives the event-driven :class:`FleetController` next to a
baseline that replans the WHOLE fleet per event — a fresh ``plan_fleet``
(or, for a VM failure, a full ``replan_on_failure`` remap).  Both sides
end at identical planned rates; the comparison is the cost of getting
there:

* **replan latency** — the incremental path re-runs only the joint level
  bisection + water-fill over cached slot surfaces (array probes; a
  ``batch_slots`` grid pass only on arrivals), the baseline recomputes
  every DAG's surface and every mapping;
* **threads migrated** — threads present before AND after an event whose
  slot changed.  The incremental delta keeps untouched DAGs bit-identical
  and repairs failures slot-for-slot; the full replan re-acquires the VM
  pool and moves nearly everything.

Writes ``BENCH_online.json`` (nightly artifact).  Targets: >= 5x lower
median latency, strictly fewer migrated threads on every non-global event
(one that leaves at least one DAG untouched).
"""

from __future__ import annotations

import statistics
import time

from repro.core import (DagArrive, DagDepart, FleetController, RateChange,
                        VmAdd, VmFail, diamond_dag, linear_dag,
                        paper_library, plan_fleet, star_dag, traffic_dag)
from repro.core.online import Event
from repro.core.scheduler import replan_on_failure

from .common import Table, write_bench_json

JSON_PATH = "BENCH_online.json"
STEP = 2.0
MAX_RATE = 2000.0
BUDGET0 = 44

MAKERS = {"linear": linear_dag, "diamond": diamond_dag, "star": star_dag,
          "traffic": traffic_dag}

#: (kind, payload) script — a bursty day on a multi-tenant fleet that
#: grows to eight DAGs.  Every DAG runs AT its offered load (demand
#: ceilings, the steady state of a production fleet); one DAG bursts past
#: what the budget can grant and gets pinned at its budget share until the
#: cluster grows.  Demand jitter that snaps to the same grid point is a
#: recorded no-op for the controller — the full baseline replans the whole
#: fleet regardless.  VmFail payloads name the DAG whose LAST VM dies (the
#: concrete id is only known at replay time); arrive payloads are (name,
#: maker, weight, priority, demand ceiling).
TRACE = [
    ("arrive", ("lin-a", "linear", 1.0, 0, 100.0)),
    ("arrive", ("dia-a", "diamond", 1.0, 0, 150.0)),
    ("arrive", ("star-a", "star", 1.0, 0, 80.0)),
    ("rate", ("lin-a", 150.0)),           # morning ramp-up
    ("arrive", ("tra-a", "traffic", 1.0, 0, 120.0)),
    ("grow", 6),
    ("arrive", ("lin-b", "linear", 1.0, 0, 60.0)),
    ("fail", "lin-a"),
    ("rate", ("star-a", 700.0)),          # burst beyond what the budget
    ("rate", ("star-a", 720.0)),          # can grant: planned rate pinned
    ("grow", 8),                          # growth feeds the burst
    ("rate", ("star-a", 80.0)),           # burst over
    ("arrive", ("star-b", "star", 1.0, 0, 70.0)),
    ("rate", ("lin-a", 151.0)),           # demand jitter: same grid point
    ("arrive", ("dia-b", "diamond", 1.0, 0, 100.0)),
    ("fail", "tra-a"),
    ("rate", ("tra-a", 60.0)),            # evening ramp-down
    ("arrive", ("tra-b", "traffic", 1.0, 0, 90.0)),
    ("depart", "lin-b"),
    ("grow", 4),
]


def trace_event(ctl: FleetController, kind: str, payload) -> Event:
    """The controller event for one TRACE entry; a ``fail`` kills the
    named DAG's last VM in ``ctl``'s current state."""
    if kind == "arrive":
        name, maker, w, p, demand = payload
        return DagArrive(name, MAKERS[maker](), weight=w, priority=p,
                         max_rate=demand)
    if kind == "depart":
        return DagDepart(payload)
    if kind == "rate":
        return RateChange(*payload)
    if kind == "grow":
        return VmAdd(payload)
    return VmFail(ctl.entry(payload).schedule.vms[-1].id)


def _replay_trace(lib, validate: bool) -> float:
    """Replay the whole TRACE through a fresh controller and return the
    summed apply() wall time — the validate-overhead probe (the verifier's
    per-event cost must stay array-level, < 10% of an incremental replan)."""
    ctl = FleetController(lib, budget_slots=BUDGET0, mapper="sam",
                          step=STEP, max_rate=MAX_RATE, validate=validate)
    total = 0.0
    for kind, payload in TRACE:
        total += ctl.apply(trace_event(ctl, kind, payload)).replan_latency_s
    return total


def _moved(prev_scheds, new_scheds) -> int:
    moved = 0
    for name, sched in new_scheds.items():
        old = prev_scheds.get(name)
        if old is None or sched is None:
            continue
        old_a = old.mapping.assignment
        moved += sum(1 for t, s in sched.mapping.assignment.items()
                     if t in old_a and old_a[t] != s)
    return moved


def run() -> dict:
    lib = paper_library()
    ctl = FleetController(lib, budget_slots=BUDGET0, mapper="sam",
                          step=STEP, max_rate=MAX_RATE)
    # the full-replan baseline's mirrored fleet state
    dags, weights, prios, caps = {}, {}, {}, {}
    budget = BUDGET0
    prev_full = {}

    tbl = Table(["event", "kind", "dags", "inc_ms", "full_ms", "speedup",
                 "inc_moved", "full_diff", "full_redeploy", "untouched"])
    rows = []
    for i, (kind, payload) in enumerate(TRACE):
        # a fail kills the DAG's LAST VM (typically the partial-bundle
        # one); the baseline repair below kills its own schedule's last VM
        event = trace_event(ctl, kind, payload)
        if kind == "arrive":
            name, maker, w, p, demand = payload
            dags[name] = MAKERS[maker]()
            weights[name], prios[name] = w, p
            if demand is not None:
                caps[name] = demand
        elif kind == "depart":
            del dags[payload], weights[payload], prios[payload]
            caps.pop(payload, None)
            prev_full.pop(payload, None)
        elif kind == "rate":
            name, ceiling = payload
            if ceiling is None:
                caps.pop(name, None)
            else:
                caps[name] = ceiling
        elif kind == "grow":
            budget += payload

        record = ctl.apply(event)
        inc_s = record.replan_latency_s

        if kind == "fail":
            # full-replan baseline for a failure: re-run the mapper over
            # the survivors + replacements (every thread may move)
            base = prev_full[payload]
            t0 = time.perf_counter()
            repaired = replan_on_failure(base, lib, [base.vms[-1].id])
            full_s = time.perf_counter() - t0
            new_full = dict(prev_full)
            new_full[payload] = repaired
        else:
            t0 = time.perf_counter()
            fp = plan_fleet(dags, lib, budget_slots=budget, mapper="sam",
                            weights=weights, priorities=prios,
                            max_rates=caps, step=STEP, max_rate=MAX_RATE)
            full_s = time.perf_counter() - t0
            new_full = {n: e.schedule for n, e in fp.entries.items()}
            got = {n: e.omega for n, e in ctl._entries.items()}
            want = {n: e.omega for n, e in fp.entries.items()}
            assert got == want, f"rate drift at event {i}: {got} != {want}"

        # two baseline migration counts: ``full_diff`` diffs placements on
        # the baseline's deterministic VM ids (charitable — a real
        # from-scratch replan has no id continuity), ``full_redeploy``
        # charges every surviving thread (a fresh §7.1 acquisition is a
        # fresh lease: everything redeploys, which is exactly what the
        # controller's keep-incumbent-VMs delta avoids)
        full_diff = _moved(prev_full, new_full)
        if kind == "fail":
            # the naive repair redeploys the one DAG it re-mapped
            full_redeploy = len(new_full[payload].mapping.assignment)
        else:
            full_redeploy = sum(
                len(s.mapping.assignment) for n, s in new_full.items()
                if s is not None and prev_full.get(n) is not None)
        prev_full = new_full

        untouched = len(record.rates) - len(record.changed)
        rows.append({"kind": kind, "inc_s": inc_s, "full_s": full_s,
                     "inc_moved": record.threads_migrated,
                     "full_diff": full_diff, "full_redeploy": full_redeploy,
                     "untouched": untouched})
        tbl.add(i, kind, len(record.rates), round(inc_s * 1e3, 2),
                round(full_s * 1e3, 2), round(full_s / inc_s, 1),
                record.threads_migrated, full_diff, full_redeploy, untouched)

    tbl.show("incremental controller vs full per-event replans "
             f"(20-event trace, budget {BUDGET0}+grows, "
             f"{len(ctl.cache.grid)}-point grid)")
    med_inc = statistics.median(r["inc_s"] for r in rows)
    med_full = statistics.median(r["full_s"] for r in rows)
    speedup = med_full / med_inc
    # non-global events leave at least one DAG untouched; on every one of
    # them the incremental delta must move strictly fewer threads than a
    # from-scratch redeploy (and no more than the charitable placement
    # diff that grants the baseline id continuity it does not really have)
    non_global = [r for r in rows if r["untouched"] > 0
                  and r["full_redeploy"] > 0]
    fewer = all(r["inc_moved"] < r["full_redeploy"] for r in non_global)
    no_worse = all(r["inc_moved"] <= r["full_diff"] for r in non_global)
    passes = ctl.cache.stats["batch_passes"]
    arrivals = sum(1 for k, _ in TRACE if k == "arrive")
    print(f"\nmedian replan latency: incremental {med_inc * 1e3:.2f} ms vs "
          f"full {med_full * 1e3:.2f} ms — {speedup:.1f}x (target >= 5x)")
    print(f"threads migrated strictly fewer than a full redeploy on all "
          f"{len(non_global)} non-global events: {fewer} "
          f"(and <= the id-continuity diff: {no_worse})")
    print(f"slot-surface grid passes: {passes} "
          f"(== {arrivals} arrivals: {passes == arrivals})")
    # validate-mode overhead: same trace, verifier off vs on (warm-up run
    # first so neither side pays one-time JIT/trace costs)
    _replay_trace(lib, validate=False)
    base_s = min(_replay_trace(lib, validate=False) for _ in range(3))
    check_s = min(_replay_trace(lib, validate=True) for _ in range(3))
    overhead = check_s / base_s - 1.0
    print(f"validate=True overhead over the 20-event trace: "
          f"{overhead * 100:.1f}% ({check_s * 1e3:.1f} ms vs "
          f"{base_s * 1e3:.1f} ms; target < 10%)")
    derived = {
        "validate_overhead_pct": round(overhead * 100, 2),
        "validate_overhead_under_10pct": overhead < 0.10,
        "median_latency_speedup": round(speedup, 1),
        "median_incremental_ms": round(med_inc * 1e3, 3),
        "median_full_ms": round(med_full * 1e3, 3),
        "non_global_events": len(non_global),
        "incremental_strictly_fewer_migrations": fewer,
        "incremental_no_worse_than_id_diff": no_worse,
        "batch_passes": passes,
        "batch_passes_equal_arrivals": passes == arrivals,
        "threads_migrated_total": sum(r["inc_moved"] for r in rows),
        "threads_full_diff_total": sum(r["full_diff"] for r in rows),
        "threads_full_redeploy_total": sum(r["full_redeploy"]
                                           for r in rows),
    }
    write_bench_json(JSON_PATH, "online_controller", derived,
                     units={"median_incremental_ms": "ms",
                            "median_full_ms": "ms",
                            "validate_overhead_pct": "pct",
                            "median_latency_speedup": "x",
                            "threads_migrated_total": "count",
                            "threads_full_diff_total": "count",
                            "threads_full_redeploy_total": "count",
                            "batch_passes": "count",
                            "non_global_events": "count"})
    return derived


def smoke() -> dict:
    """Tier-1-safe controller smoke: a 3-event trace whose rates must match
    a full ``plan_fleet`` of the final state, with one grid pass per
    arrival and none for the rate change."""
    lib = paper_library()
    ctl = FleetController(lib, budget_slots=12, mapper=None,
                          step=10.0, max_rate=500.0)
    ctl.apply(DagArrive("linear", linear_dag()))
    ctl.apply(DagArrive("diamond", diamond_dag()))
    ctl.apply(RateChange("linear", 50.0))
    fp = plan_fleet({"linear": linear_dag(), "diamond": diamond_dag()}, lib,
                    budget_slots=12, mapper=None,
                    max_rates={"linear": 50.0}, step=10.0, max_rate=500.0)
    got = {n: e.omega for n, e in ctl._entries.items()}
    want = {n: e.omega for n, e in fp.entries.items()}
    assert got == want, f"incremental != full: {got} vs {want}"
    assert ctl.cache.stats["batch_passes"] == 2
    print(f"online-controller smoke OK: 3-event trace, rates {got} match "
          "full plan_fleet, 2 surface passes")
    return {"smoke_ok": True}


if __name__ == "__main__":
    run()

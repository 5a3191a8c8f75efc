"""Replay a bursty day through the online elastic fleet controller.

Three tenants share one slot budget.  Over the day their offered load
ramps up, one DAG bursts past what the cluster can grant, a VM dies mid-
morning, and two more tenants arrive — every event handled by ONE
incremental replan over cached slot surfaces (a ``batch_slots`` grid pass
runs only when a DAG first arrives).  After each event the live fleet is
co-simulated in one batched sweep and the ControllerLog timeline prints
planned rates, threads migrated, and replan latency per event.

Run:  PYTHONPATH=src python examples/online_controller.py
"""

from repro.core import (DagArrive, DagDepart, EventTrace, FleetController,
                        RateChange, RoutingPolicy, VmAdd, VmFail,
                        diamond_dag, linear_dag, paper_library, star_dag)
from repro.jaxenv import init_compile_cache


def main() -> None:
    lib = paper_library()
    # slot-aware routing: the §11 policy whose simulated behaviour tracks
    # the plan (shuffle would show the known planned-vs-actual gap)
    ctl = FleetController(lib, budget_slots=24, objective="max_min",
                          mapper="sam", step=10.0, max_rate=1000.0,
                          policy=RoutingPolicy.SLOT_AWARE)

    # the day opens with two tenants; "linear" is demand-capped, "diamond"
    # elastically soaks the leftover budget
    ctl.apply(DagArrive("linear", linear_dag(), max_rate=80.0), at=0.0)
    ctl.apply(DagArrive("diamond", diamond_dag()), at=0.5)

    # linear is demand-capped, so its VMs survive the morning ramp intact
    vm_to_fail = ctl.entry("linear").schedule.vms[0].id
    day = EventTrace([
        (9.0, RateChange("linear", 150.0)),     # morning ramp-up
        (10.5, VmFail(vm_to_fail)),             # a host dies
        (11.0, DagArrive("star", star_dag(), weight=2.0)),   # new tenant
        (12.0, RateChange("linear", 600.0)),    # lunch burst: budget-bound
        (13.0, VmAdd(8)),                       # ops grows the cluster
        (15.0, RateChange("linear", 90.0)),     # burst over
        (17.0, DagArrive("traffic-lite", linear_dag(), max_rate=60.0)),
        (22.0, DagDepart("star")),              # evening wind-down
    ])
    ctl.replay(day, simulate=True, fractions=[0.5, 1.0], duration=6.0,
               dt=0.1, warmup=2.0, engine="numpy")

    print(ctl.log.describe())
    print()
    print(ctl.plan.describe())
    passes = ctl.cache.stats["batch_passes"]
    print(f"\nslot-surface grid passes all day: {passes} "
          f"(one per arrival; every other replan was array probes on "
          "cached surfaces)")


if __name__ == "__main__":
    init_compile_cache()
    main()

"""Fleet predicted-vs-actual walkthrough on the jitted sweep simulator.

Plans a four-DAG fleet against one shared slot budget, then co-simulates
every planned DAG's rate sweep in ONE batched ``lax.scan`` call on the
shared VM pool — under both routing policies (§11) — and compares:

* per DAG: the planner's rate vs the §8.5 predicted max vs the simulated
  actual max stable rate;
* per VM: predicted CPU/mem (§8.5.2 model surfaces) vs the actual draw
  derived from what each thread group really served.

Run:  python examples/fleet_simulate.py
"""

import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import (RoutingPolicy, diamond_dag, linear_dag,
                        paper_library, plan_fleet, simulate_fleet, star_dag,
                        traffic_dag)
from repro.jaxenv import init_compile_cache

BUDGET = 32


def main() -> None:
    models = paper_library()
    dags = {"linear": linear_dag(), "diamond": diamond_dag(),
            "star": star_dag(), "traffic": traffic_dag()}
    fleet = plan_fleet(dags, models, budget_slots=BUDGET,
                       objective="max_min")
    print(fleet.describe())

    # co-simulate the whole fleet: one jitted time loop per policy, every
    # DAG swept over 0.25..1.25 of its planned rate simultaneously
    reports = {}
    for policy in RoutingPolicy:
        print(f"\n--- routing = {policy.value} ---")
        rep = reports[policy] = simulate_fleet(fleet, models, duration=20.0,
                                               dt=0.05, engine="scan",
                                               policy=policy)
        print(rep.describe())

        # stability along each DAG's sweep: where does the fleet actually
        # tip over, relative to the planner's promise?
        print("stability across the sweep (fractions of planned rate):")
        fracs = " ".join(f"{f:5.2f}" for f in rep.fractions)
        print(f"  {'DAG':8s} {fracs}")
        for name, e in rep.entries.items():
            marks = " ".join("   ok" if r.stable else " OVER"
                             for r in e.results)
            print(f"  {name:8s} {marks}")

    # the busiest slots of the shared pool at the planned operating point
    # (the plan's own policy is shuffle — reuse that report)
    rep = reports[fleet.policy]
    busiest = sorted(rep.slot_busy.items(), key=lambda kv: -kv[1])[:5]
    print("\nbusiest slots at the planned rates (shared pool; values sum "
          "the slot's per-group utilizations, so multi-group slots can "
          "exceed 1.0):")
    for slot, busy in busiest:
        print(f"  {slot}: {busy:.2f} group-busy")


if __name__ == "__main__":
    init_compile_cache()
    main()

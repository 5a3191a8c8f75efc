"""Fleet planning walkthrough: many DAGs, one cluster budget.

Plans the paper's three micro DAGs plus the Traffic application against a
shared 32-slot cluster under each fleet objective, then shows the per-VM
predicted resource report and what a budget cut preempts first.

Run:  python examples/fleet_plan.py

(For the empirical leg — co-simulating the planned fleet on the jitted
sweep engine and comparing predicted vs actual — see
``examples/fleet_simulate.py``.)
"""

import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import (diamond_dag, fleet_resource_surfaces, linear_dag,
                        paper_library, plan_fleet, star_dag, traffic_dag)
from repro.jaxenv import init_compile_cache

BUDGET = 32


def main() -> None:
    models = paper_library()
    dags = {"linear": linear_dag(), "diamond": diamond_dag(),
            "star": star_dag(), "traffic": traffic_dag()}

    # 1. max-min fairness: every tenant's rate raised together
    fp = plan_fleet(dags, models, budget_slots=BUDGET, objective="max_min")
    print(fp.describe())

    # 2. weighted shares: 'linear' is a paying tenant worth 3x
    fw = plan_fleet(dags, models, budget_slots=BUDGET, objective="weighted",
                    weights={"linear": 3.0})
    print()
    print(fw.describe())

    # 3. priority tiers: traffic is production, micro DAGs are batch tiers
    fpr = plan_fleet(dags, models, budget_slots=12, objective="priority",
                     priorities={"traffic": 2, "linear": 1})
    print()
    print(fpr.describe())
    print(f"preemption order under budget pressure: "
          f"{' -> '.join(fpr.preemption_order())}")

    # 4. fleet-level predicted load per VM (the §8.5.2 report, array passes)
    print("\nper-VM predicted load (max-min plan):")
    for vm in sorted(fp.vm_cpu):
        print(f"  vm{vm}: cpu {fp.vm_cpu[vm] * 100:6.1f}%  "
              f"mem {fp.vm_mem[vm] * 100:6.1f}%")

    # 5. whole CPU surfaces over each DAG's rate sweep, one array pass each
    surfaces = fleet_resource_surfaces(fp, models)
    print("\npredicted fleet CPU at fractions of the planned rates:")
    for name, sweep in surfaces.items():
        total = sweep.vm_cpu.sum(axis=0)
        mid = len(total) // 2
        print(f"  {name:8s}: {total[mid]:5.2f} slots at "
              f"{sweep.omegas[mid]:g} t/s -> {total[-1]:5.2f} slots at "
              f"{sweep.omegas[-1]:g} t/s")


if __name__ == "__main__":
    init_compile_cache()
    main()

"""Simulation-guided mapper search walkthrough.

Generates the whole candidate-mapping pool for one DAG (DSM/RSM/SAM, RSM
weight sweeps, seeded swap/migrate local moves), scores every candidate's
full rate sweep in ONE shape-bucketed ``jax.vmap``-ed scan program, and
ranks them by the simulated max stable rate — then shows the same engine as
a drop-in ``plan(mapper="search")`` and as the fleet planner's opt-in
refinement pass.

Run:  python examples/mapper_search.py
"""

import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import (RoutingPolicy, diamond_dag, linear_dag,
                        paper_library, plan, plan_fleet, search_mapping)
from repro.core.simulator import scan_kernel_cache_stats
from repro.jaxenv import init_compile_cache


def main() -> None:
    models = paper_library()
    dag = diamond_dag()

    # 1. the raw search: every candidate's sweep through one vmapped kernel
    #    per shape bucket, ranked by empirical max stable rate
    ranked = search_mapping(dag, 100, models, n_moves=8,
                            policy=RoutingPolicy.SHUFFLE)
    print(ranked.describe())
    for name in ("dsm", "rsm", "sam"):
        gain = ranked.gain_over(name)
        if gain is not None:
            print(f"  search gain over {name}: +{gain:g} t/s")
    print(f"kernel cache after the search: {scan_kernel_cache_stats()}")

    # 2. as a scheduler mapper: an ordinary Schedule whose mapping is the
    #    simulation-picked winner
    s = plan(dag, 100, models, allocator="mba", mapper="search")
    print(f"\n{s.describe()}")

    # 3. as a fleet refinement pass: each planned DAG's base mapping
    #    competes against the pool on its own pinned VM subset
    stats = {}
    fleet = plan_fleet({"linear": linear_dag(), "diamond": diamond_dag()},
                       models, budget_slots=12, refine_search=True,
                       stats=stats)
    print(f"\n{fleet.describe()}")
    print(f"refinement: {stats['search_candidates']} candidates evaluated, "
          f"{stats['search_improved']} DAG mappings improved")
    for e in fleet.entries.values():
        if e.schedule and e.schedule.search_winner:
            print(f"  {e.name}: mapped by {e.schedule.search_winner} "
                  f"(via search)")


if __name__ == "__main__":
    init_compile_cache()
    main()

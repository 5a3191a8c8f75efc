"""Benchmark aggregator — one function per paper table/figure.

Prints a ``name,us_per_call,derived`` CSV summary line per benchmark after
each benchmark's own detailed table.  ``--smoke`` runs only the tier-1-safe
jitted-engine smoke (tiny grid, asserts scan==numpy) so CI catches compile
regressions fast.
"""

from __future__ import annotations

import json
import sys

from repro.jaxenv import init_compile_cache

from . import (bench_app_dags, bench_chaos, bench_fleet, bench_latency,
               bench_mapper_search, bench_micro_dags, bench_obs,
               bench_online, bench_perfmodels, bench_predictability,
               bench_prove, bench_sweep)
from .common import timed

BENCHES = [
    ("fig3_perfmodels", bench_perfmodels.run),
    ("fig7_micro_dags", bench_micro_dags.run),
    ("fig8_app_dags", bench_app_dags.run),
    ("fig9_12_predictability", bench_predictability.run),
    ("fig13_latency", bench_latency.run),
    ("sweep_engine", bench_sweep.run),
    ("mapper_search", bench_mapper_search.run),
    ("fleet_planner", bench_fleet.run),
    ("fleet_cost_frontier", bench_fleet.cost_frontier),
    ("online_controller", bench_online.run),
    ("obs_telemetry", bench_obs.run),
    ("chaos_enactment", bench_chaos.run),
    ("rate_prover", bench_prove.run),
]


def main() -> None:
    init_compile_cache()
    if "--smoke" in sys.argv[1:]:
        # CI smoke runs with the repro.analysis verifier on: every plan the
        # smokes build is integrity-checked before it is simulated
        from repro.core import set_default_validate
        set_default_validate(True)
        rows = []
        for name, fn in (("sweep_smoke", bench_sweep.smoke),
                         ("mapper_search_smoke", bench_mapper_search.smoke),
                         ("online_controller_smoke", bench_online.smoke),
                         ("obs_smoke", bench_obs.smoke),
                         ("chaos_smoke", bench_chaos.smoke),
                         ("rate_prover_smoke", bench_prove.smoke),
                         ("fleet_cost_smoke", bench_fleet.smoke)):
            derived, us = timed(fn)
            rows.append((name, us, derived))
        print("\nname,us_per_call,derived")
        for name, us, derived in rows:
            print(f"{name},{us:.0f},"
                  f"{json.dumps(derived, separators=(';', ':'))}")
        return
    only = sys.argv[1] if len(sys.argv) > 1 else None
    rows = []
    for name, fn in BENCHES:
        if only and only not in name:
            continue
        derived, us = timed(fn)
        rows.append((name, us, derived))
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{json.dumps(derived, separators=(';', ':'))}")


if __name__ == "__main__":
    main()

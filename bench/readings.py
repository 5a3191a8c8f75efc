"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 12 --seconds 5

For each seed, in one process: the cell's set-up and a short window at the
cell's own load, then every number that decides ``correct`` twice, for the
program (the lower reading) and for the control, the reference one
precision below what the configuration states (the upper reading).  One
JSON line per seed, then the largest program reading and the smallest
control reading of each number.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    _, cell, config, traffic, limits = bench_run.cell_files(args.workload)
    try:
        devices = bench_run.devices_for(int(cell["chips"]), True)
    except bench_run.NoChip as err:
        print(f"bench/readings.py: {err}", file=sys.stderr)
        return 2
    from repro.jaxenv import init_compile_cache
    init_compile_cache()

    load_class = bench_run.load_class(traffic["load"])
    lower: dict = {}
    upper: dict = {}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        load = load_class(config, traffic, seed, devices, limits)
        load.setup(args.seconds)
        load.run(args.seconds)
        load.release()
        program = {c.name: c.value for c in load.check()}
        ctl = {c.name: c.value for c in load.check(control=True)}
        for name, v in program.items():
            lower[name] = max(lower.get(name, v), v)
        for name, v in ctl.items():
            upper[name] = min(upper.get(name, v), v)
        print(json.dumps({"seed": seed, "items": len(load.items),
                          "program": program, "control": ctl}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

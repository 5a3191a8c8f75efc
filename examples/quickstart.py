"""Quickstart: the paper's model-driven scheduler in ~40 lines.

Profile tasks (Alg. 1) -> allocate with MBA -> map with SAM -> predict the
supported rate (§8.5) -> check against the simulator -> enact the schedule
on JAX devices.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

from repro.core import (DataflowSimulator, diamond_dag, paper_library, plan)
from repro.jaxenv import init_compile_cache
from repro.runtime import StreamExecutor

TARGET_RATE = 100.0  # tuples/sec the dataflow must sustain


def main() -> None:
    # 1. performance models (pre-profiled Fig. 3 curves; see
    #    repro.core.profiler.profile_task to build your own via Alg. 1)
    models = paper_library()

    # 2. the streaming application: a fan-out/fan-in micro-DAG
    dag = diamond_dag()

    # 3. plan: Model-Based Allocation + Slot-Aware Mapping
    schedule = plan(dag, TARGET_RATE, models, allocator="mba", mapper="sam")
    print(schedule.describe())
    print(f"price: ${schedule.price_per_hour:.2f}/hour")

    # 4. model-driven prediction of what the schedule actually sustains
    predicted = schedule.predicted_rate(models)
    print(f"predicted stable rate: {predicted:.1f} t/s "
          f"(planned {TARGET_RATE:g})")

    # 5. cross-check with the fluid simulator ("actual")
    sim = DataflowSimulator(dag, schedule.allocation, schedule.mapping, models)
    actual = sim.max_stable_rate(duration=15, dt=0.1)
    print(f"simulated stable rate: {actual:.1f} t/s")

    # 6. enact on real JAX devices (each slot pinned to a device)
    report = StreamExecutor(schedule, models).run(TARGET_RATE, duration=1.5)
    print(f"enacted: {report.throughput:.1f} t/s over {report.frames} frames, "
          f"mean latency {report.mean_latency * 1e3:.1f} ms, "
          f"stable={report.stable}")


if __name__ == "__main__":
    init_compile_cache()
    main()

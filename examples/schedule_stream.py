"""Multi-device streaming-DSPS demo: plan a schedule for a real application
DAG and enact it across 8 forced host devices (each resource slot pinned to
its own device), comparing shuffle vs slot-aware routing.

Run:  python examples/schedule_stream.py        (sets its own XLA_FLAGS)
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.core import RoutingPolicy, paper_library, plan, traffic_dag
from repro.jaxenv import init_compile_cache
from repro.runtime import StreamExecutor


def main() -> None:
    print(f"devices: {len(jax.devices())}")
    models = paper_library()
    dag = traffic_dag()
    schedule = plan(dag, 60, models, allocator="mba", mapper="sam")
    print(schedule.describe())

    for policy in (RoutingPolicy.SHUFFLE, RoutingPolicy.SLOT_AWARE):
        rep = StreamExecutor(schedule, models, policy=policy).run(
            60, duration=1.5, batch=16)
        print(f"{policy.value:10s}: {rep.throughput:6.1f} t/s  "
              f"mean latency {rep.mean_latency*1e3:6.1f} ms  "
              f"devices used: {len(rep.device_frame_counts)}")


if __name__ == "__main__":
    init_compile_cache()
    main()

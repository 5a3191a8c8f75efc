"""End-to-end behaviour tests for the paper's system.

The full loop: profile (Alg. 1) -> allocate (MBA) -> map (SAM) -> predict
(§8.5) -> simulate -> ENACT on real JAX devices.
"""

from repro.core import (DataflowSimulator, RoutingPolicy, diamond_dag,
                        paper_library, plan)
from repro.core.profiler import profiled_library
from repro.runtime import StreamExecutor


def test_full_paper_pipeline_profiled_models():
    """Alg.1-built models drive MBA+SAM to a stable, enactable schedule."""
    lib = profiled_library(["parse_xml", "pi", "batch_file_write",
                            "azure_blob", "azure_table"])
    dag = diamond_dag()
    schedule = plan(dag, 60, lib, allocator="mba", mapper="sam")
    assert schedule.acquired_slots <= 12
    pred = schedule.predicted_rate(lib)
    assert pred > 30
    sim = DataflowSimulator(dag, schedule.allocation, schedule.mapping, lib)
    res = sim.run(min(pred, 60) * 0.8, duration=15, dt=0.1)
    assert res.stable


def test_executor_enacts_schedule():
    """The JAX streaming executor sustains the planned rate end-to-end on
    real devices (single CPU device hosts all slots here)."""
    lib = paper_library()
    dag = diamond_dag()
    schedule = plan(dag, 80, lib, allocator="mba", mapper="sam")
    ex = StreamExecutor(schedule, lib)
    rep = ex.run(80, duration=1.0, batch=16)
    assert rep.tuples > 0
    assert rep.throughput > 40          # sustains most of the target rate
    assert rep.stable


def test_executor_slot_aware_routing():
    lib = paper_library()
    dag = diamond_dag()
    schedule = plan(dag, 60, lib, allocator="mba", mapper="sam")
    ex = StreamExecutor(schedule, lib, policy=RoutingPolicy.SLOT_AWARE)
    rep = ex.run(60, duration=0.8, batch=16)
    assert rep.tuples > 0 and rep.stable

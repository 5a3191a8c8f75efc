"""Fault tolerance: replanning a schedule off failed VMs."""

from repro.core import (DataflowSimulator, diamond_dag, paper_library, plan,
                        replan_on_failure)


def test_replan_survives_vm_failure():
    """Kill a VM: one deterministic replan restores a stable schedule with
    every thread remapped off the failed host."""
    lib = paper_library()
    dag = diamond_dag()
    s = plan(dag, 100, lib, allocator="mba", mapper="sam")
    failed = s.vms[0].id
    s2 = replan_on_failure(s, lib, [failed])
    # no thread lands on the failed VM
    for slot in s2.mapping.assignment.values():
        assert slot.vm != failed
    # same allocation (model-driven), all threads mapped
    assert len(s2.mapping.assignment) == s.allocation.total_threads
    # and the recovered schedule is still stable at ~the same rate
    sim = DataflowSimulator(dag, s2.allocation, s2.mapping, lib)
    assert sim.run(80, duration=15, dt=0.1).stable


def test_replan_multiple_failures():
    lib = paper_library()
    dag = diamond_dag()
    s = plan(dag, 100, lib, allocator="mba", mapper="sam")
    failed = [vm.id for vm in s.vms[:2]]
    s2 = replan_on_failure(s, lib, failed)
    for slot in s2.mapping.assignment.values():
        assert slot.vm not in failed

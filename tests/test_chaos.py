"""Chaos-hardened enactment: determinism, robustness machinery, and the
measure→recalibrate loop.

Everything runs on a :class:`VirtualClock` with operator time priced from
the model tables, so fault timelines, controller event sequences, and
measured rates are all deterministic — the replay pins are *bit*-exact,
not statistical.
"""

import numpy as np
import pytest

from repro.core import (DagArrive, EventTrace, FleetController, ModelLibrary,
                        PerfModel, RateChange, TaskMeasurement, detect_drift,
                        diamond_dag, paper_library, plan, rate_error,
                        recalibrate)
from repro.core.perfmodel import ModelPoint
from repro.runtime import (ExecutionReport, Fault, FaultKind, FaultPlan,
                           LiveFleet, RobustnessPolicy, StreamExecutor,
                           VirtualClock, transplant_map)

BUDGET = 24


def _controller(lib, budget=BUDGET):
    return FleetController(lib, budget_slots=budget)


def _trace():
    return EventTrace([
        (0.0, DagArrive("d1", diamond_dag(), max_rate=80.0)),
        (1.0, DagArrive("d2", diamond_dag(), max_rate=60.0)),
        (2.0, RateChange("d1", 50.0)),
    ])


def _bursty_plan(seed=7):
    return FaultPlan.from_seed(
        seed, dags=["d1", "d2"], tasks=["b", "c"], horizon_frames=20,
        operator_errors=2, slowdowns=2, drops=1)


# -- determinism -------------------------------------------------------------

def test_fault_plan_from_seed_deterministic():
    assert _bursty_plan(7) == _bursty_plan(7)
    assert _bursty_plan(7) != _bursty_plan(8)


def test_identical_seed_bit_identical_replay(lib):
    """Same FaultPlan seed ⇒ bit-identical fault timelines AND identical
    controller event sequences across two full replays."""
    def run():
        fleet = LiveFleet(_controller(lib), fault_plan=_bursty_plan(),
                          clock=VirtualClock())
        log = fleet.replay(_trace())
        return log
    a, b = run(), run()
    assert len(a.timeline) > 0
    assert a.timeline.signature() == b.timeline.signature()
    assert a.rates_sequence() == b.rates_sequence()
    assert ([r.controller.kind for r in a.records]
            == [r.controller.kind for r in b.records])
    # measured windows are deterministic too
    for ra, rb in zip(a.records, b.records):
        for name in ra.reports:
            assert ra.reports[name].throughput == rb.reports[name].throughput
            assert ra.reports[name].frames_shed == rb.reports[name].frames_shed


# -- the fault-free no-op rail ----------------------------------------------

def test_fault_free_round_trip_matches_headless_replay(lib):
    headless = _controller(lib).replay(_trace())
    fleet = LiveFleet(_controller(lib), fault_plan=FaultPlan.none(),
                      clock=VirtualClock())
    live = fleet.replay(_trace())
    assert live.rates_sequence() == [dict(r.rates) for r in headless.records]
    assert len(live.timeline) == 0
    for rec in live.records:
        assert not rec.escalations and not rec.repairs
    # the identity rail: the executors hold the controller's exact objects
    for name in fleet.ctl.dag_names:
        assert fleet.executors[name].schedule is fleet.ctl.entry(name).schedule


def test_recalibration_on_exact_profiles_is_bit_identical(lib):
    """Measured rates priced from the planning tables themselves leave
    recalibration a provable no-op: the very same PerfModel objects."""
    fleet = LiveFleet(_controller(lib), fault_plan=FaultPlan.none(),
                      clock=VirtualClock())
    fleet.replay(_trace())
    assert len(fleet.measurements()) > 0
    result = fleet.recalibrate()
    assert result.changed_kinds == []
    for kind in lib.kinds():
        assert result.library[kind] is lib[kind]
    assert result.error_before < 1e-9


# -- robustness machinery ----------------------------------------------------

def test_retry_absorbs_transient_operator_errors(lib):
    plan_f = FaultPlan(faults=(
        Fault(FaultKind.OPERATOR_ERROR, frame=3, dag="d1", task="b", count=2),
    ))
    fleet = LiveFleet(_controller(lib), fault_plan=plan_f,
                      clock=VirtualClock(), frames_per_event=8)
    rec = fleet.apply(DagArrive("d1", diamond_dag(), max_rate=80.0), at=0.0)
    rep = rec.reports["d1"]
    assert rep.retries >= 2              # two failing attempts, then success
    assert rep.frames_failed == 0        # no tuple was lost
    assert rep.tuples_lost == 0
    assert not rec.escalations


def test_dropped_frames_are_shed_not_fatal(lib):
    plan_f = FaultPlan(faults=(
        Fault(FaultKind.DROP_FRAME, frame=2, dag="d1", frames=2),
    ))
    fleet = LiveFleet(_controller(lib), fault_plan=plan_f,
                      clock=VirtualClock(), frames_per_event=8)
    rec = fleet.apply(DagArrive("d1", diamond_dag(), max_rate=80.0), at=0.0)
    rep = rec.reports["d1"]
    assert rep.frames_shed == 2
    assert rep.frames == 8
    assert rep.stable                     # the survivors are healthy


def test_degenerate_window_reports_reason_instead_of_crashing(lib):
    """Satellite: zero post-warmup latency samples must not crash p99/slope
    and must report stable=False with an explicit reason."""
    schedule = plan(diamond_dag(), 80, lib, allocator="mba", mapper="sam")
    ex = StreamExecutor(schedule, lib, clock=VirtualClock())
    rep = ex.run(80, n_frames=1, batch=16, warmup_frames=2)
    assert rep.frames == 1
    assert rep.stable is False
    assert "no post-warmup latency samples" in rep.stable_reason
    assert rep.p99_latency == 0.0 and rep.latency_slope == 0.0


def test_correlated_two_vm_failure_escalates_and_transplants(lib):
    """Acceptance rail: correlated 2-VM crash → breaker escalates both VMs
    to VmFail, repair transplants ONLY failed-VM slots (asserted by slot
    id), and post-recovery throughput is within 10%% of the planned rate."""
    probe = _controller(lib)
    probe.apply(DagArrive("d1", diamond_dag(), max_rate=200.0))
    base_sched = probe.entry("d1").schedule
    assert len(base_sched.vms) >= 2       # the scenario needs 2 VMs to kill
    original_slots = set(base_sched.mapping.slots())
    original_vms = {vm.id for vm in base_sched.vms}

    plan_f = FaultPlan(faults=(
        Fault(FaultKind.VM_CRASH, frame=8, dag="d1", vm_index=0),
        Fault(FaultKind.VM_CRASH, frame=8, dag="d1", vm_index=1),
    ))
    fleet = LiveFleet(_controller(lib), fault_plan=plan_f,
                      clock=VirtualClock(), frames_per_event=16)
    rec = fleet.apply(DagArrive("d1", diamond_dag(), max_rate=200.0), at=0.0)

    # both crashed VMs escalated through the breaker into synthetic VmFail
    assert sorted(vm for _, vm in rec.escalations) == sorted(original_vms)
    assert len(rec.repairs) == len(original_vms)

    # repair restarted ONLY replacement slots: every restarted/transplant
    # target lives on a fresh VM, every surviving original slot kept its op
    info = rec.rebound["d1"]
    for slot in info.restarted_slots:
        assert slot.vm not in original_vms
    for old, new in info.transplanted.items():
        assert old in original_slots and old.vm in original_vms
        assert new.vm not in original_vms
    assert info.fresh_ops == 0            # pure transplant, zero re-jits

    # the repaired fleet re-converges to the planned rate
    recovery = rec.recovery_reports["d1"]
    planned = fleet.ctl.entry("d1").omega
    assert recovery.frames_failed == 0
    assert abs(recovery.throughput - planned) / planned <= 0.10


def test_circuit_breaker_threshold(lib):
    """A persistently failing slot trips after exactly breaker_threshold
    consecutive frame failures and is skipped afterwards."""
    schedule = plan(diamond_dag(), 80, lib, allocator="mba", mapper="sam")
    plan_f = FaultPlan(faults=(
        Fault(FaultKind.VM_CRASH, frame=2, dag="d", vm_index=0),
    ))
    from repro.runtime import FaultInjector
    inj = FaultInjector(plan_f, "d")
    ex = StreamExecutor(schedule, lib, faults=inj, clock=VirtualClock(),
                        robustness=RobustnessPolicy(breaker_threshold=3))
    rep = ex.run(80, n_frames=10, batch=16)
    assert rep.escalated_vms == (schedule.vms[0].id,)
    assert schedule.vms[0].id in ex.tripped_vms


def test_transplant_map_identity_and_remap():
    lib = paper_library()
    sched = plan(diamond_dag(), 80, lib, allocator="mba", mapper="sam")
    assert transplant_map(sched, sched) == {}


# -- the measure -> recalibrate loop -----------------------------------------

def _doubled(lib):
    """A deliberately mis-profiled library: every rate 2x the truth."""
    out = ModelLibrary()
    for kind in lib.kinds():
        m = lib[kind]
        out.add(PerfModel(kind, [ModelPoint(p.tau, p.rate * 2.0, p.cpu, p.mem)
                                 for p in m.points], static=m.static))
    return out


def test_recalibration_closes_2x_error(lib):
    """On a 2x-off table, one recalibration pass drops measured-vs-predicted
    rate error by >= 5x (the acceptance criterion, unit-level)."""
    wrong = _doubled(lib)
    ctl = FleetController(wrong, budget_slots=BUDGET)
    fleet = LiveFleet(ctl, fault_plan=FaultPlan.none(), clock=VirtualClock(),
                      truth=lib)           # reality runs at the TRUE rates
    fleet.apply(DagArrive("d1", diamond_dag(), max_rate=80.0), at=0.0)
    ms = fleet.measurements()
    assert ms
    result = recalibrate(wrong, ms, alpha=0.9)
    assert result.error_before > 0.4       # ~|0.5 - 1|
    assert result.error_after <= result.error_before / 5.0
    # and the grid/cpu/mem columns survived (verifier-clean by conftest's
    # process-wide validate, exercised again explicitly)
    from repro.analysis import verify_calibration
    assert verify_calibration(wrong, result) == []


def test_rate_error_and_drift_detection(lib):
    ms = [TaskMeasurement(kind="pi", task="c", tau=1, tuples=100.0,
                          busy_seconds=100.0 / lib["pi"].I(1))]
    assert rate_error(lib, ms) < 1e-9
    rep_bad = ExecutionReport(
        omega=80.0, frames=8, tuples=0, wall_seconds=1.0, throughput=0.0,
        mean_latency=0.0, p99_latency=0.0, latency_slope=0.5, stable=False,
        device_frame_counts={}, stable_reason="latency slope 0.5 rising")
    alerts = detect_drift({"d1": True}, {"d1": rep_bad})
    assert len(alerts) == 1
    assert alerts[0].dag == "d1"
    assert alerts[0].predicted_stable and not alerts[0].measured_stable
    assert detect_drift({"d1": False}, {"d1": rep_bad}) == []


def test_device_error_propagates_instead_of_counting_lost_tuples(lib):
    """Only the modelled InjectedOperatorError is retried: any other error
    from an operator (a compile, memory or device fault) ends the run
    instead of being counted as lost tuples."""
    schedule = plan(diamond_dag(), 80, lib, allocator="mba", mapper="sam")
    ex = StreamExecutor(schedule, lib, clock=VirtualClock())
    key = next(iter(ex._ops))

    def broken(part):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    ex._ops[key] = broken
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        ex.run(80, n_frames=4, batch=16)


def test_executor_pins_slots_to_the_callers_devices(lib):
    """Slots round-robin over the given device list; every routed part
    runs there and the sink outputs live there."""
    import jax
    dev = jax.devices()[-1]
    schedule = plan(diamond_dag(), 80, lib, allocator="mba", mapper="sam")
    ex = StreamExecutor(schedule, lib, clock=VirtualClock(), devices=[dev])
    rep = ex.run(80, n_frames=4, batch=16, warmup_frames=1)
    assert set(ex.slot_device.values()) == {dev}
    assert set(rep.device_frame_counts) == {str(dev)}
    assert ex.last_sink_outputs
    for arrays in ex.last_sink_outputs.values():
        for v in arrays.values():
            assert v.devices() == {dev}
    with pytest.raises(ValueError, match="at least one device"):
        StreamExecutor(schedule, lib, devices=[])


def test_live_fleet_defaults_to_the_wall_clock(lib):
    from repro.runtime import WallClock
    fleet = LiveFleet(_controller(lib), frames_per_event=0)
    assert isinstance(fleet.clock, WallClock)

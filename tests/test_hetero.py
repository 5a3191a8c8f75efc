"""Heterogeneous, cost-aware VM classes: the bit-identity equivalence rail
(unit classes must reproduce the plain-int plans exactly), the ``min_cost``
objective pinned against brute-force budget partitions, §6 speed-scaling
semantics, the self-sizing controller, like-for-like failure replacement,
and acquisition properties."""

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:        # property tests skip; plain tests still run
    from _hypothesis_fallback import hypothesis, st

import itertools

import numpy as np
import pytest

from repro.core import (DagArrive, DagDepart, FleetController, RateChange,
                        VmAdd, VmClass, acquire_vms, batch_slots, diamond_dag,
                        linear_dag, mapping_signature, paper_library, plan,
                        plan_fleet, replan_on_failure, star_dag,
                        vm_class_family, vm_classes_from_sizes)
from repro.core.mapping import (PRICE_PER_SLOT_HOUR, pool_cost_per_hour,
                                pool_speed, resolve_vm_classes,
                                vm_sizes_speed)

STEP, MAX_RATE = 10.0, 300.0


@pytest.fixture(scope="module")
def lib():
    return paper_library()


def _pool_shape(vms):
    """The comparison key of the equivalence rail: class metadata aside,
    unit-class pools must be the plain pools."""
    return [(vm.id, vm.num_slots, vm.rack, vm.speed) for vm in vms]


# -- VmClass model ------------------------------------------------------------

def test_vm_class_defaults():
    c = VmClass("d4", 4)
    assert c.cost_per_hour == pytest.approx(4 * PRICE_PER_SLOT_HOUR)
    assert c.speed == 1.0 and c.mem_per_slot == 1.0


@pytest.mark.parametrize("kwargs", [
    {"slots": 0}, {"slots": -2}, {"slots": 2, "speed": 0.0},
    {"slots": 2, "speed": -1.0}, {"slots": 2, "cost_per_hour": -0.1},
    {"slots": 2, "mem_per_slot": 0.0},
])
def test_vm_class_rejects_bad_params(kwargs):
    with pytest.raises(ValueError):
        VmClass("bad", **kwargs)


def test_resolve_vm_classes_forms():
    ints = resolve_vm_classes((4, 2, 1))
    assert [c.slots for c in ints] == [4, 2, 1]
    assert resolve_vm_classes("azure-d") == vm_class_family("azure-d")
    assert resolve_vm_classes(ints) == ints
    with pytest.raises(ValueError):
        resolve_vm_classes(())
    with pytest.raises(ValueError):
        resolve_vm_classes("no-such-family")


def test_mixed_speed_specs_rejected():
    mixed = (VmClass("a", 4, speed=1.0), VmClass("b", 2, speed=2.0))
    with pytest.raises(ValueError):
        vm_sizes_speed(mixed)
    with pytest.raises(ValueError):
        acquire_vms(6, mixed)


# -- acquisition --------------------------------------------------------------

def test_unit_classes_acquire_bit_identical():
    """Regime 2 (uniform $/slot classes) must reproduce the §7.1 greedy,
    rack assignment included."""
    unit = vm_classes_from_sizes((4, 2, 1))
    for rho in range(1, 80):
        plain = acquire_vms(rho, (4, 2, 1))
        tagged = acquire_vms(rho, unit)
        assert _pool_shape(plain) == _pool_shape(tagged), rho
        assert all(vm.vm_class for vm in tagged)


def test_acquire_covers_minimally():
    for sizes in ((4, 2, 1), (8, 4, 2, 1), (3, 1)):
        for rho in range(1, 60):
            total = sum(vm.num_slots for vm in acquire_vms(rho, sizes))
            assert rho <= total < rho + max(sizes)


def test_acquire_min_cost_dp_beats_greedy_when_prices_skew():
    """rho=8 with a cheap 5-slot and an expensive 4-slot: the greedy's
    [5, 4] costs 1.3, the DP's [5, 5] costs 1.0."""
    classes = (VmClass("five", 5, cost_per_hour=0.5),
               VmClass("four", 4, cost_per_hour=0.8))
    vms = acquire_vms(8, classes)
    assert sorted(vm.num_slots for vm in vms) == [5, 5]
    assert pool_cost_per_hour(vms) == pytest.approx(1.0)


def _brute_force_min_cost_cover(rho, classes):
    def better(a, b):
        # tolerance on the float cost so the (n_vms, slots) tie-breaks
        # decide true ties, matching acquire_vms's DP comparison
        if a[0] < b[0] - 1e-9:
            return True
        if a[0] > b[0] + 1e-9:
            return False
        return a[1:] < b[1:]

    best = None
    bounds = [range(-(-rho // c.slots) + 1) for c in classes]
    for counts in itertools.product(*bounds):
        slots = sum(n * c.slots for n, c in zip(counts, classes))
        if slots < rho:
            continue
        key = (sum(n * c.cost_per_hour for n, c in zip(counts, classes)),
               sum(counts), slots)
        if best is None or better(key, best):
            best = key
    return best


CLASS_SETS = [
    (VmClass("five", 5, cost_per_hour=0.5),
     VmClass("four", 4, cost_per_hour=0.8)),
    (VmClass("big", 8, cost_per_hour=0.6),
     VmClass("mid", 3, cost_per_hour=0.3),
     VmClass("one", 1, cost_per_hour=0.2)),
    (VmClass("a", 7, cost_per_hour=1.0),
     VmClass("b", 2, cost_per_hour=0.5)),
]


@pytest.mark.parametrize("classes", CLASS_SETS,
                         ids=["5v4", "8-3-1", "7v2"])
def test_acquire_min_cost_matches_brute_force(classes):
    for rho in range(1, 30):
        vms = acquire_vms(rho, classes)
        cost, n, slots = _brute_force_min_cost_cover(rho, classes)
        assert pool_cost_per_hour(vms) == pytest.approx(cost), rho
        assert len(vms) == n and sum(v.num_slots for v in vms) == slots


@hypothesis.given(rho=st.integers(min_value=1, max_value=64),
                  sizes=st.lists(st.integers(min_value=1, max_value=9),
                                 min_size=1, max_size=4, unique=True))
@hypothesis.settings(max_examples=60, deadline=None)
def test_acquire_property_covers_and_racks(rho, sizes):
    """Every regime covers rho exactly or minimally over, never splits a
    VM across racks, and unit classes shadow the plain path."""
    plain = acquire_vms(rho, tuple(sizes), rack_size=8)
    total = sum(vm.num_slots for vm in plain)
    assert rho <= total < rho + max(sizes)
    assert [vm.rack for vm in plain] == [vm.id // 8 for vm in plain]
    tagged = acquire_vms(rho, vm_classes_from_sizes(tuple(sizes)),
                         rack_size=8)
    assert _pool_shape(plain) == _pool_shape(tagged)


@hypothesis.given(rho=st.integers(min_value=1, max_value=24),
                  costs=st.lists(
                      st.floats(min_value=0.05, max_value=2.0,
                                allow_nan=False), min_size=2, max_size=3))
@hypothesis.settings(max_examples=40, deadline=None)
def test_acquire_property_cost_minimal(rho, costs):
    slots = (5, 3, 2)[:len(costs)]
    classes = tuple(VmClass(f"c{s}", s, cost_per_hour=c)
                    for s, c in zip(slots, costs))
    vms = acquire_vms(rho, classes)
    assert sum(vm.num_slots for vm in vms) >= rho
    best_cost = _brute_force_min_cost_cover(rho, classes)[0]
    assert pool_cost_per_hour(vms) == pytest.approx(best_cost)


# -- the equivalence rail -----------------------------------------------------

FLEET_KW = dict(step=STEP, max_rate=MAX_RATE)


def _fleet_dags():
    return {"linear": linear_dag(), "diamond": diamond_dag(),
            "star": star_dag()}


@pytest.mark.parametrize("objective", ["max_min", "weighted", "priority"])
def test_plan_fleet_unit_classes_bit_identical(lib, objective):
    """A unit-speed, unit-cost class family of sizes (4,2,1) reproduces the
    plain-int plan exactly: rates, pools, mappings, for every objective."""
    kw = dict(FLEET_KW)
    if objective == "weighted":
        kw["weights"] = {"linear": 2.0, "diamond": 1.0, "star": 3.0}
    if objective == "priority":
        kw["priorities"] = {"linear": 1, "diamond": 0, "star": 2}
    a = plan_fleet(_fleet_dags(), lib, budget_slots=20, objective=objective,
                   vm_sizes=(4, 2, 1), **kw)
    b = plan_fleet(_fleet_dags(), lib, budget_slots=20, objective=objective,
                   vm_sizes=vm_classes_from_sizes((4, 2, 1)), **kw)
    for n in a.entries:
        ea, eb = a.entries[n], b.entries[n]
        assert ea.omega == eb.omega
        assert ea.estimated_slots == eb.estimated_slots
        if ea.schedule is None:
            assert eb.schedule is None
            continue
        assert mapping_signature(ea.schedule.mapping) == \
            mapping_signature(eb.schedule.mapping)
    assert _pool_shape(a.pool) == _pool_shape(b.pool)
    assert np.array_equal(a.slots_matrix, b.slots_matrix)


def test_plan_unit_classes_bit_identical(lib):
    a = plan(linear_dag(), 120.0, lib, vm_sizes=(4, 2, 1))
    b = plan(linear_dag(), 120.0, lib,
             vm_sizes=vm_classes_from_sizes((4, 2, 1)))
    assert a.omega == b.omega
    assert a.estimated_slots == b.estimated_slots
    assert _pool_shape(a.vms) == _pool_shape(b.vms)
    assert mapping_signature(a.mapping) == mapping_signature(b.mapping)


def test_controller_unit_classes_bit_identical(lib):
    """Replaying one trace on plain-int and unit-class controllers (the
    ``replan_incremental`` + delta path) produces identical rates, pools,
    and mappings at every event."""
    def build(vm_sizes):
        return FleetController(lib, budget_slots=18, step=STEP,
                               max_rate=MAX_RATE, vm_sizes=vm_sizes)
    ca, cb = build((4, 2, 1)), build(vm_classes_from_sizes((4, 2, 1)))
    events = [DagArrive("linear", linear_dag(), max_rate=150.0),
              DagArrive("star", star_dag()),
              RateChange("linear", 60.0),
              DagDepart("star")]
    for ev in events:
        ra, rb = ca.apply(ev), cb.apply(ev)
        assert ra.rates == rb.rates
        assert ra.fleet_cost_per_hour == pytest.approx(rb.fleet_cost_per_hour)
        assert _pool_shape(ca.pool) == _pool_shape(cb.pool)
        for n in ca.dag_names:
            sa, sb = ca.entry(n).schedule, cb.entry(n).schedule
            assert (sa is None) == (sb is None)
            if sa is not None:
                assert mapping_signature(sa.mapping) == \
                    mapping_signature(sb.mapping)


# -- min_cost objective -------------------------------------------------------

COST_CLASSES = (VmClass("big", 8, cost_per_hour=0.60),
                VmClass("small", 2, cost_per_hour=0.20))


def _cost_tables(dags, lib, classes):
    """Independent recomputation of the per-DAG cost rows: min over
    classes of ``ceil(slots / c.slots) * c.cost_per_hour``."""
    grid = STEP * np.arange(1, int(MAX_RATE / STEP) + 1)
    tables = {}
    for name, dag in dags.items():
        rows = []
        for c in classes:
            slots = batch_slots(dag, grid, lib, "mba",
                                clip_unsupportable=True, speed=c.speed,
                                mem_per_slot=c.mem_per_slot)
            cost = -(-slots // c.slots) * c.cost_per_hour
            rows.append(np.where(slots >= 2 ** 61, np.inf, cost))
        tables[name] = np.min(np.stack(rows), axis=0)
    return grid, tables


def _brute_force_min_cost_rates(dags, lib, classes, budget):
    """Lexicographically best sorted rate vector over every per-DAG grid
    index combination whose total $/hour fits the budget."""
    grid, tables = _cost_tables(dags, lib, classes)
    names = list(dags)
    best = None
    choices = [range(-1, len(grid)) for _ in names]
    for combo in itertools.product(*choices):
        cost = sum(0.0 if k < 0 else tables[n][k]
                   for n, k in zip(names, combo))
        if cost > budget + 1e-9:
            continue
        rates = tuple(sorted(0.0 if k < 0 else float(grid[k])
                             for k in combo))
        if best is None or rates > best:
            best = rates
    return best


@pytest.mark.parametrize("dag_names,budget", [
    (("linear", "diamond"), 1.0),
    (("linear", "diamond"), 2.2),
    (("linear", "diamond", "star"), 1.6),
], ids=["2dags-$1", "2dags-$2.2", "3dags-$1.6"])
def test_min_cost_matches_brute_force_partition(lib, dag_names, budget):
    all_dags = _fleet_dags()
    dags = {n: all_dags[n] for n in dag_names}
    fp = plan_fleet(dags, lib, budget_dollars=budget, objective="min_cost",
                    mapper=None, vm_sizes=COST_CLASSES, **FLEET_KW)
    got = tuple(sorted(e.omega for e in fp.entries.values()))
    assert got == _brute_force_min_cost_rates(dags, lib, COST_CLASSES, budget)
    spent = sum(e.est_cost_per_hour for e in fp.entries.values())
    assert spent <= budget + 1e-9


def test_min_cost_acquires_winning_classes(lib):
    fp = plan_fleet(_fleet_dags(), lib, budget_dollars=2.5,
                    objective="min_cost", vm_sizes=COST_CLASSES, **FLEET_KW)
    names = {c.name for c in COST_CLASSES}
    by_name = {c.name: c for c in COST_CLASSES}
    for e in fp.entries.values():
        if e.schedule is None:
            continue
        assert e.vm_class in names
        c = by_name[e.vm_class]
        # pool = winning-class VMs, plus possibly §8.4 +1-slot retry VMs
        assert all((vm.num_slots == c.slots and vm.vm_class == c.name)
                   or vm.num_slots == 1
                   for vm in e.schedule.vms)
        assert any(vm.vm_class == c.name for vm in e.schedule.vms)
        n_vms = -(-e.estimated_slots // c.slots)
        assert e.est_cost_per_hour == pytest.approx(n_vms * c.cost_per_hour)
    assert fp.cost_per_hour == pool_cost_per_hour(fp.pool)
    assert "budget=$" in fp.describe()


def test_min_cost_argument_validation(lib):
    dags = {"linear": linear_dag()}
    with pytest.raises(ValueError):      # dollar budget required
        plan_fleet(dags, lib, budget_slots=10, objective="min_cost",
                   vm_sizes=COST_CLASSES, **FLEET_KW)
    with pytest.raises(ValueError):      # slot objectives take slot budgets
        plan_fleet(dags, lib, budget_dollars=1.0, objective="max_min",
                   vm_sizes=(4, 2, 1), **FLEET_KW)


def test_min_cost_rejected_by_replan_incremental(lib):
    from repro.core import SlotSurfaceCache, replan_incremental
    cache = SlotSurfaceCache(step=STEP, max_rate=MAX_RATE)
    cache.surface("linear", linear_dag(), lib)
    with pytest.raises(ValueError, match="min_cost"):
        replan_incremental(cache, ["linear"], budget_slots=10,
                           objective="min_cost")


# -- speed semantics ----------------------------------------------------------

FAST = (VmClass("f4", 4, speed=2.0, cost_per_hour=1.0),
        VmClass("f1", 1, speed=2.0, cost_per_hour=0.30))


def test_speed_shrinks_slot_demand(lib):
    grid = STEP * np.arange(1, int(MAX_RATE / STEP) + 1)
    unit = batch_slots(linear_dag(), grid, lib, "mba",
                       clip_unsupportable=True)
    fast = batch_slots(linear_dag(), grid, lib, "mba",
                       clip_unsupportable=True, speed=2.0)
    assert np.all(fast <= unit)
    # speed=2 at rate 2w needs exactly what speed=1 needs at w
    assert np.array_equal(
        batch_slots(linear_dag(), grid * 2, lib, "mba",
                    clip_unsupportable=True, speed=2.0),
        unit)


def test_plan_on_fast_class_verifies_and_predicts(lib):
    from repro.analysis import verify_schedule
    from repro.core import build_group_index, predict_max_rate_gi
    from repro.core.routing import RoutingPolicy
    sched = plan(linear_dag(), 200.0, lib, vm_sizes=FAST)
    assert sched.omega == 200.0
    assert pool_speed(sched.vms) == 2.0
    assert verify_schedule(sched) == []
    unit_sched = plan(linear_dag(), 200.0, lib)
    assert sched.estimated_slots < unit_sched.estimated_slots
    # the §8.4.1 capacity fold-in: the same placement demoted to unit
    # speed predicts exactly half the ceiling
    import dataclasses
    from repro.core import Mapping
    gi = build_group_index(sched.dag, sched.allocation, sched.mapping, lib,
                          RoutingPolicy.SHUFFLE)
    slow = Mapping([dataclasses.replace(vm, speed=1.0) for vm in sched.vms])
    for thread, slot in sched.mapping.assignment.items():
        slow.assign(thread, slot)
    gi_slow = build_group_index(sched.dag, sched.allocation, slow, lib,
                                RoutingPolicy.SHUFFLE)
    assert predict_max_rate_gi(gi) == 2 * predict_max_rate_gi(gi_slow) > 0


def test_prover_carries_speed_bounds(lib):
    """The static rate prover reads the speed-scaled ``g_cap``: a plan that
    is only stable BECAUSE of speed-2 slots proves stable, and the same
    placement demoted to unit speed does not."""
    import dataclasses
    from repro.analysis.prove import PROVED_STABLE, prove_group_index
    from repro.core import build_group_index
    from repro.core.routing import RoutingPolicy
    sched = plan(linear_dag(), 200.0, lib, vm_sizes=FAST)
    gi = build_group_index(sched.dag, sched.allocation, sched.mapping, lib,
                           RoutingPolicy.SHUFFLE)
    assert prove_group_index(gi, 150.0, name="fast").verdict == PROVED_STABLE
    from repro.core import Mapping
    slow = Mapping([dataclasses.replace(vm, speed=1.0) for vm in sched.vms])
    for thread, slot in sched.mapping.assignment.items():
        slow.assign(thread, slot)
    gi_slow = build_group_index(sched.dag, sched.allocation, slow, lib,
                                RoutingPolicy.SHUFFLE)
    assert prove_group_index(gi_slow, 150.0,
                             name="slow").verdict != PROVED_STABLE


# -- like-for-like failure replacement ---------------------------------------

def test_replan_on_failure_preserves_vm_classes(lib):
    sched = plan(linear_dag(), 200.0, lib, vm_sizes=FAST)
    assert len(sched.vms) >= 2
    victim = max(sched.vms, key=lambda vm: vm.num_slots)
    repaired = replan_on_failure(sched, lib, [victim.id])
    assert all(vm.id != victim.id for vm in repaired.vms)
    old = sorted((vm.num_slots, vm.speed, vm.vm_class) for vm in sched.vms)
    new = sorted((vm.num_slots, vm.speed, vm.vm_class) for vm in repaired.vms)
    assert new == old            # like-for-like, not re-packed to defaults


def test_replan_on_failure_like_for_like_plain(lib):
    """Plain §7.1 pools too: a failed 4-slot VM is replaced by a 4-slot VM
    even when the default acquisition would have chosen differently."""
    sched = plan(linear_dag(), 150.0, lib, vm_sizes=(4, 2, 1))
    sizes = sorted(vm.num_slots for vm in sched.vms)
    victim = max(sched.vms, key=lambda vm: vm.num_slots)
    repaired = replan_on_failure(sched, lib, [victim.id])
    assert sorted(vm.num_slots for vm in repaired.vms) == sizes


# -- self-sizing controller ---------------------------------------------------

def test_self_size_controller_tracks_demand(lib):
    ctl = FleetController(lib, self_size=True, step=STEP, max_rate=MAX_RATE,
                          vm_sizes=(4, 2, 1))
    r1 = ctl.apply(DagArrive("linear", linear_dag(), max_rate=200.0))
    assert ctl.budget_slots >= 1 and r1.fleet_cost_per_hour > 0
    r2 = ctl.apply(DagArrive("star", star_dag(), max_rate=150.0))
    assert r2.fleet_cost_per_hour > r1.fleet_cost_per_hour
    # rate drop: budget shrinks, emptied VMs released, $/hour falls
    r3 = ctl.apply(RateChange("linear", 60.0))
    assert r3.fleet_cost_per_hour < r2.fleet_cost_per_hour
    # depart: every emptied VM released, $/hour strictly decreases
    r4 = ctl.apply(DagDepart("star"))
    assert r4.fleet_cost_per_hour < r3.fleet_cost_per_hour
    assert all(vm in ctl.entry("linear").schedule.vms for vm in ctl.pool)
    # the log carries the dollar timeline
    assert [r.fleet_cost_per_hour for r in ctl.log.records] == \
        [r1.fleet_cost_per_hour, r2.fleet_cost_per_hour,
         r3.fleet_cost_per_hour, r4.fleet_cost_per_hour]
    assert "$" in ctl.log.describe()


def test_self_size_budget_matches_demand_ceilings(lib):
    ctl = FleetController(lib, self_size=True, step=STEP, max_rate=MAX_RATE,
                          mapper=None)
    ctl.apply(DagArrive("linear", linear_dag(), max_rate=100.0))
    ctl.apply(DagArrive("diamond", diamond_dag(), max_rate=50.0))
    want = sum(int(ctl.cache.row(n)[int(np.searchsorted(
        ctl.cache.grid, m * (1 + 1e-12), side="right")) - 1])
        for n, m in (("linear", 100.0), ("diamond", 50.0)))
    assert ctl.budget_slots == want
    # every DAG gets exactly its ceiling (nobody competes: budget==demand)
    assert ctl.log.records[-1].rates == {"linear": 100.0, "diamond": 50.0}


def test_self_size_event_guards(lib):
    with pytest.raises(ValueError):      # budget and self_size are exclusive
        FleetController(lib, budget_slots=10, self_size=True)
    with pytest.raises(ValueError):      # one of them is required
        FleetController(lib)
    ctl = FleetController(lib, self_size=True, step=STEP, max_rate=MAX_RATE,
                          mapper=None)
    with pytest.raises(ValueError):      # arrivals must pin a ceiling
        ctl.apply(DagArrive("linear", linear_dag()))
    ctl.apply(DagArrive("linear", linear_dag(), max_rate=80.0))
    with pytest.raises(ValueError):      # it owns its budget
        ctl.apply(VmAdd(4))
    with pytest.raises(ValueError):      # ceilings cannot be unpinned
        ctl.apply(RateChange("linear", None))
    assert ctl.dag_names == ["linear"]


def test_controller_speed_class_family(lib):
    """A speed-2 family controller plans on speed-aware surfaces: same
    rates as the unit controller at half-ish the slots, verifier clean."""
    unit = FleetController(lib, budget_slots=40, step=STEP,
                           max_rate=MAX_RATE)
    fast = FleetController(lib, budget_slots=40, step=STEP,
                           max_rate=MAX_RATE, vm_sizes=FAST)
    for ctl in (unit, fast):
        ctl.apply(DagArrive("linear", linear_dag(), max_rate=200.0))
    e_u, e_f = unit.entry("linear"), fast.entry("linear")
    assert e_f.omega == e_u.omega == 200.0
    assert e_f.estimated_slots < e_u.estimated_slots
    assert pool_speed(fast.pool) == 2.0

"""Fleet planner: joint rates vs brute-force budget partitions, the sweep
predictor vs per-rate predictions, and shared-pool accounting.

The brute force enumerates every way to split the slot budget across the
DAGs, gives each DAG its §8.5 scan-optimal rate for its share, and compares
the fleet planner's joint result against the best split — the planner must
match while doing only one vectorized grid pass per DAG.
"""

import itertools

import numpy as np
import pytest

from repro.core import (MICRO_DAGS, RoutingPolicy, batch_slots,
                        build_group_index, diamond_dag, linear_dag,
                        paper_library, plan, plan_fleet, predict_resources,
                        predict_resources_sweep, fleet_resource_surfaces,
                        star_dag, traffic_dag)
from repro.core.batch import prefix_feasible_count

STEP = 10.0
MAX_RATE = 1000.0


@pytest.fixture(scope="module")
def lib():
    return paper_library()


def _grid():
    return STEP * np.arange(1, int(MAX_RATE / STEP) + 1)


def _best_rate_by_budget(dag, lib, budget):
    """R[b] = the §8.5 scan answer for a dedicated budget of b slots
    (largest leading-prefix rate whose slot estimate fits b)."""
    grid = _grid()
    slots = batch_slots(dag, grid, lib, "mba", clip_unsupportable=True)
    out = np.zeros(budget + 1)
    for b in range(budget + 1):
        n = prefix_feasible_count(slots <= b)
        out[b] = grid[n - 1] if n > 0 else 0.0
    return out


def _brute_force_max_min(dags, lib, budget):
    """Lexicographically best sorted rate vector over ALL budget splits."""
    tables = [_best_rate_by_budget(d, lib, budget) for d in dags.values()]
    best = None
    for split in itertools.product(range(budget + 1), repeat=len(tables)):
        if sum(split) > budget:
            continue
        rates = tuple(sorted(t[b] for t, b in zip(tables, split)))
        if best is None or rates > best:
            best = rates
    return best


FLEETS = [
    ({"linear": linear_dag(), "diamond": diamond_dag()}, 12),
    ({"linear": linear_dag(), "diamond": diamond_dag(),
      "star": star_dag()}, 8),
    ({"linear": linear_dag(), "diamond": diamond_dag(),
      "star": star_dag()}, 17),
    ({"linear": linear_dag(), "diamond": diamond_dag(), "star": star_dag(),
      "traffic": traffic_dag()}, 14),
]


@pytest.mark.parametrize("dags,budget", FLEETS,
                         ids=[f"{len(d)}dags-{b}slots" for d, b in FLEETS])
def test_max_min_matches_brute_force_partition(lib, dags, budget):
    """Acceptance: the joint planner's max-min rates equal the best possible
    dedicated-budget split (2-4 DAG fleets on the seed models)."""
    fp = plan_fleet(dags, lib, budget_slots=budget, objective="max_min",
                    mapper=None, step=STEP, max_rate=MAX_RATE)
    got = tuple(sorted(e.omega for e in fp.entries.values()))
    assert got == _brute_force_max_min(dags, lib, budget)
    assert fp.total_estimated_slots <= budget


def test_weighted_min_ratio_matches_brute_force(lib):
    """The weighted objective maximizes the worst rate/weight ratio over all
    budget splits; equal weights reduce to max_min exactly."""
    dags = {"linear": linear_dag(), "diamond": diamond_dag(),
            "star": star_dag()}
    weights = {"linear": 2.0, "diamond": 1.0, "star": 1.0}
    budget = 20
    fp = plan_fleet(dags, lib, budget_slots=budget, objective="weighted",
                    weights=weights, mapper=None,
                    step=STEP, max_rate=MAX_RATE)
    got_min = min(e.omega / weights[n] for n, e in fp.entries.items())
    tables = {n: _best_rate_by_budget(d, lib, budget)
              for n, d in dags.items()}
    best_min = 0.0
    names = list(dags)
    for split in itertools.product(range(budget + 1), repeat=len(names)):
        if sum(split) > budget:
            continue
        best_min = max(best_min, min(tables[n][b] / weights[n]
                                     for n, b in zip(names, split)))
    assert got_min == pytest.approx(best_min)

    eq = plan_fleet(dags, lib, budget_slots=budget, objective="weighted",
                    mapper=None, step=STEP, max_rate=MAX_RATE)
    mm = plan_fleet(dags, lib, budget_slots=budget, objective="max_min",
                    mapper=None, step=STEP, max_rate=MAX_RATE)
    assert {n: e.omega for n, e in eq.entries.items()} == \
        {n: e.omega for n, e in mm.entries.items()}


def _brute_force_weighted_lex(dags, lib, budget, weights):
    """Lexicographically best sorted ratio vector over ALL budget splits."""
    tables = {n: _best_rate_by_budget(d, lib, budget)
              for n, d in dags.items()}
    names = list(dags)
    best = None
    for split in itertools.product(range(budget + 1), repeat=len(names)):
        if sum(split) > budget:
            continue
        vec = tuple(sorted(tables[n][b] / weights[n]
                           for n, b in zip(names, split)))
        if best is None or vec > best:
            best = vec
    return best


@pytest.mark.parametrize("weights,budget", [
    ({"linear": 2.0, "diamond": 1.0, "star": 1.5}, 12),
    ({"linear": 3.0, "diamond": 1.0, "star": 1.0}, 9),
    ({"linear": 1.0, "diamond": 2.5}, 14),
], ids=["3dags-12", "3dags-9", "2dags-14"])
def test_weighted_unequal_exact_lexicographic(lib, weights, budget):
    """Acceptance: with UNEQUAL weights the whole sorted ratio vector —
    not just the minimum — equals the brute-force optimum over every
    budget split (the exact bottleneck water-fill, ROADMAP item)."""
    dags = {n: {"linear": linear_dag, "diamond": diamond_dag,
                "star": star_dag}[n]() for n in weights}
    fp = plan_fleet(dags, lib, budget_slots=budget, objective="weighted",
                    weights=weights, mapper=None,
                    step=STEP, max_rate=MAX_RATE)
    got = tuple(sorted(e.omega / weights[n] for n, e in fp.entries.items()))
    want = _brute_force_weighted_lex(dags, lib, budget, weights)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert fp.total_estimated_slots <= budget


def test_max_rates_cap_releases_budget(lib):
    """A demand ceiling clamps the capped DAG to the grid point at or
    below it and hands the freed slots to the rest of the fleet."""
    dags = {"linear": linear_dag(), "diamond": diamond_dag()}
    free = plan_fleet(dags, lib, budget_slots=14, mapper=None,
                      step=STEP, max_rate=MAX_RATE)
    capped = plan_fleet(dags, lib, budget_slots=14, mapper=None,
                        max_rates={"linear": 55.0},
                        step=STEP, max_rate=MAX_RATE)
    assert capped.entries["linear"].omega == 50.0
    assert capped.entries["diamond"].omega >= free.entries["diamond"].omega
    # a zero ceiling is a throttle, not an admission failure
    off = plan_fleet(dags, lib, budget_slots=14, mapper=None,
                     max_rates={"linear": 0.0},
                     step=STEP, max_rate=MAX_RATE)
    assert off.entries["linear"].omega == 0.0


def test_unsupportable_dag_raises_typed_error(lib):
    from repro.core import UnsupportableDagError
    dags = {"linear": linear_dag(), "diamond": diamond_dag()}
    with pytest.raises(UnsupportableDagError) as err:
        plan_fleet(dags, lib, budget_slots=2, mapper=None,
                   step=100.0, max_rate=MAX_RATE)
    assert err.value.dag in dags
    assert err.value.budget_slots == 2


def test_surface_cache_skips_grid_passes(lib):
    """A warm SlotSurfaceCache makes plan_fleet's grid passes free and the
    planned rates identical to the uncached path."""
    from repro.core import SlotSurfaceCache
    dags = {"linear": linear_dag(), "diamond": diamond_dag()}
    cache = SlotSurfaceCache(allocator="mba", step=STEP, max_rate=MAX_RATE)
    s1, s2 = {}, {}
    fp1 = plan_fleet(dags, lib, budget_slots=12, mapper=None,
                     surface_cache=cache, stats=s1,
                     step=STEP, max_rate=MAX_RATE)
    fp2 = plan_fleet(dags, lib, budget_slots=12, mapper=None,
                     surface_cache=cache, stats=s2,
                     step=STEP, max_rate=MAX_RATE)
    assert s1["batch_passes"] == 2 and s2["batch_passes"] == 0
    assert {n: e.omega for n, e in fp1.entries.items()} == \
        {n: e.omega for n, e in fp2.entries.items()}
    with pytest.raises(ValueError):
        plan_fleet(dags, lib, budget_slots=12, mapper=None,
                   surface_cache=cache, allocator="lsa",
                   step=STEP, max_rate=MAX_RATE)
    with pytest.raises(ValueError):
        plan_fleet(dags, lib, budget_slots=12, mapper=None,
                   surface_cache=cache, step=STEP * 2, max_rate=MAX_RATE)
    # a structurally different DAG under a cached name is refused, a
    # rebuilt-but-identical DAG object is a legitimate hit
    with pytest.raises(ValueError):
        cache.surface("linear", star_dag(), lib)
    cache.surface("linear", linear_dag(), lib)


def test_priority_tiers_and_preemption_order(lib):
    """Strict tiers: the top tier gets its solo optimum, the bottom tier is
    preempted first when the budget is tight."""
    dags = {"linear": linear_dag(), "diamond": diamond_dag(),
            "star": star_dag()}
    prios = {"linear": 2, "diamond": 1, "star": 0}
    budget = 12
    fp = plan_fleet(dags, lib, budget_slots=budget, objective="priority",
                    priorities=prios, mapper=None,
                    step=STEP, max_rate=MAX_RATE)
    solo = _best_rate_by_budget(dags["linear"], lib, budget)[budget]
    assert fp.entries["linear"].omega == solo
    used = fp.entries["linear"].estimated_slots
    solo_diamond = _best_rate_by_budget(dags["diamond"], lib,
                                        budget)[budget - used]
    assert fp.entries["diamond"].omega == solo_diamond
    # whatever is left goes to the lowest tier
    assert fp.entries["star"].omega <= fp.entries["diamond"].omega
    order = fp.preemption_order()
    running = [n for n, e in fp.entries.items() if e.omega > 0]
    assert order[0] == "star" if "star" in running else True
    assert order[-1] == "linear"


def test_fleet_mapping_shares_one_pool(lib):
    """Full pipeline: per-DAG schedules on fleet-unique VMs, acquisition
    close to the planning budget, §8.5.2 predictions attached."""
    dags = {n: mk() for n, mk in MICRO_DAGS.items()}
    stats = {}
    fp = plan_fleet(dags, lib, budget_slots=24, objective="max_min",
                    stats=stats, step=STEP, max_rate=MAX_RATE)
    assert stats["batch_passes"] == len(dags)
    # one scalar allocator call per mapping attempt, a handful total —
    # nothing like the O(rate/step) §8.5 scan
    assert stats["allocator_calls"] <= 3 * len(dags)
    all_vm_ids = [vm.id for e in fp.entries.values() if e.schedule
                  for vm in e.schedule.vms]
    assert len(all_vm_ids) == len(set(all_vm_ids))       # fleet-unique ids
    assert fp.total_estimated_slots <= 24
    assert fp.total_acquired_slots <= 24 + 2 * len(dags)  # §8.4-style extras
    assert fp.overflow_slots == max(0, fp.total_acquired_slots - 24)
    for e in fp.entries.values():
        assert e.schedule is not None
        assert e.schedule.omega == e.omega
        assert e.prediction is not None
        # the prediction covers exactly this DAG's share of the pool
        assert set(e.prediction.vm_cpu) == {vm.id for vm in e.schedule.vms}
    # fleet-level per-VM report covers the whole pool's used VMs
    assert set(fp.vm_cpu) == set(all_vm_ids)


def test_per_dag_model_libraries(lib):
    dags = {"linear": linear_dag(), "diamond": diamond_dag()}
    fp = plan_fleet(dags, {"linear": lib, "diamond": lib}, budget_slots=12,
                    objective="max_min", mapper=None,
                    step=STEP, max_rate=MAX_RATE)
    shared = plan_fleet(dags, lib, budget_slots=12, objective="max_min",
                        mapper=None, step=STEP, max_rate=MAX_RATE)
    assert {n: e.omega for n, e in fp.entries.items()} == \
        {n: e.omega for n, e in shared.entries.items()}


def test_fleet_argument_validation(lib):
    dags = {"linear": linear_dag()}
    with pytest.raises(ValueError):
        plan_fleet(dags, lib, budget_slots=10, objective="nope")
    with pytest.raises(ValueError):
        plan_fleet(dags, lib, budget_slots=0)
    with pytest.raises(ValueError):
        plan_fleet({}, lib, budget_slots=10)
    with pytest.raises(ValueError):
        plan_fleet(dags, lib, budget_slots=10, weights={"linear": -1.0})


# -- vectorized §8.5.2 predictor vs per-rate predictions ----------------------

@pytest.mark.parametrize("policy", [RoutingPolicy.SHUFFLE,
                                    RoutingPolicy.SLOT_AWARE])
def test_predict_resources_sweep_matches_scalar(lib, policy):
    """Acceptance: the (S, K)/(V, K) surfaces equal per-rate
    predict_resources to 1e-12 on a 50-point grid."""
    for mk in (linear_dag, star_dag):
        dag = mk()
        s = plan(dag, 100, lib, allocator="mba", mapper="sam")
        gi = build_group_index(dag, s.allocation, s.mapping, lib, policy)
        omegas = np.linspace(2.0, 150.0, 50)
        sweep = predict_resources_sweep(gi, omegas, mapping=s.mapping)
        assert sweep.slot_cpu.shape == (len(sweep.slots), 50)
        assert sweep.vm_cpu.shape == (len(sweep.vm_ids), 50)
        assert set(sweep.slots) == set(s.mapping.slots())
        for k in range(50):
            ref = predict_resources(dag, s.allocation, s.mapping, lib,
                                    float(omegas[k]), policy)
            col = sweep.at(k)
            for slot in ref.slot_cpu:
                assert col.slot_cpu[slot] == pytest.approx(
                    ref.slot_cpu[slot], rel=1e-12, abs=1e-12)
                assert col.slot_mem[slot] == pytest.approx(
                    ref.slot_mem[slot], rel=1e-12, abs=1e-12)
            for vm in ref.vm_cpu:
                assert col.vm_cpu[vm] == pytest.approx(
                    ref.vm_cpu[vm], rel=1e-12, abs=1e-12)
                assert col.vm_mem[vm] == pytest.approx(
                    ref.vm_mem[vm], rel=1e-12, abs=1e-12)


def test_fleet_resource_surfaces(lib):
    dags = {n: mk() for n, mk in MICRO_DAGS.items()}
    fp = plan_fleet(dags, lib, budget_slots=24, objective="max_min",
                    step=STEP, max_rate=MAX_RATE)
    surfaces = fleet_resource_surfaces(fp, lib)
    for name, sweep in surfaces.items():
        e = fp.entries[name]
        assert sweep.omegas[-1] == e.omega
        # the surface's final column is the entry's attached prediction
        for vm, cpu in e.prediction.vm_cpu.items():
            row = sweep.vm_ids.index(vm)
            assert sweep.vm_cpu[row, -1] == pytest.approx(cpu)


def test_simulate_fleet_report(lib):
    """The fleet study's invariants: every mapped DAG gets a sweep anchored
    at its planned rate, max-stable is one of the swept rates, and actual
    per-VM draw stays at or below the §8.5.2 prediction (proportional
    scale-down of the same C/M on served <= routed rates)."""
    from repro.core import simulate_fleet
    dags = {"linear": linear_dag(), "diamond": diamond_dag()}
    fp = plan_fleet(dags, lib, budget_slots=12)
    rep = simulate_fleet(fp, lib, duration=8.0, dt=0.1, engine="numpy")
    assert rep.at_fraction == 1.0
    assert set(rep.entries) == set(dags)
    assert not rep.skipped
    for name, e in rep.entries.items():
        assert e.omega_planned == fp.entries[name].omega
        assert len(e.results) == len(rep.fractions)
        np.testing.assert_allclose(e.omegas,
                                   rep.fractions * e.omega_planned)
        assert e.actual_max_stable in set(e.omegas) | {0.0}
        assert e.predicted_max_rate > 0
        # low fractions of a budget-feasible plan must simulate stable
        assert e.results[0].stable
    vms = {vm.id for vm in fp.pool}
    assert set(rep.vm_cpu_predicted) == vms
    for vm in vms:
        assert rep.vm_cpu_actual[vm] <= rep.vm_cpu_predicted[vm] + 1e-9
        assert rep.vm_mem_actual[vm] <= rep.vm_mem_predicted[vm] + 1e-9
    assert rep.slot_busy
    assert rep.describe()


def test_simulate_fleet_rejects_unmapped_plan(lib):
    fp = plan_fleet({"linear": linear_dag()}, lib, budget_slots=12,
                    mapper=None)
    from repro.core import simulate_fleet
    with pytest.raises(ValueError):
        simulate_fleet(fp, lib)

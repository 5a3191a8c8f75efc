"""Simulation-guided mapper search: vmapped candidate engine vs the numpy
reference, candidate-pool invariants, and the search-beats-single-mapper pin.

The contract mirrors the scan engine's: the shape-bucketed ``jax.vmap``
evaluation must reproduce per-candidate ``engine="numpy"`` tick loops to
<= 1e-10 on every raw surface, for a pool spanning several shape buckets, on
every DAG of ``ALL_DAGS`` and under both routing policies — and because
every single §7 mapper is itself a candidate, ``mapper="search"`` can never
return a worse simulated max stable rate than the best of DSM/RSM/SAM on the
same pool.
"""

import numpy as np
import pytest

from repro.core import (ALL_DAGS, DataflowSimulator, RoutingPolicy,
                        diamond_dag, linear_dag, paper_library, plan,
                        plan_fleet)
from repro.core.allocation import ALLOCATORS
from repro.core.mapping import (local_moves, make_threads, mapping_signature)
from repro.core.search import (evaluate_candidates, generate_candidates,
                               search_mapping)
from repro.core.simulator import scan_kernel_cache_stats

RAW_FIELDS = ("queues", "busy", "served", "realized", "latency")
TINY = dict(duration=4.0, dt=0.1)


@pytest.fixture(scope="module")
def lib():
    return paper_library()


@pytest.fixture(scope="module")
def pool(lib, request):
    """One shared (dag, alloc, vms, candidates) fixture per DAG: the
    diamond unless a test parametrises it by ``ALL_DAGS`` name."""
    dag = ALL_DAGS[getattr(request, "param", "diamond")]()
    alloc = ALLOCATORS["mba"](dag, 100, lib)
    ranked = search_mapping(dag, 100, lib, n_moves=2, rate_fractions=[1.0],
                            duration=1.0, dt=0.5)
    cands = generate_candidates(dag, alloc, ranked.vms, lib, n_moves=2)
    return dag, alloc, ranked.vms, cands


# -- vmapped engine equivalence ------------------------------------------------

@pytest.mark.parametrize("pool", list(ALL_DAGS), indirect=True)
@pytest.mark.parametrize("policy", list(RoutingPolicy),
                         ids=[p.value for p in RoutingPolicy])
def test_vmap_matches_per_candidate_numpy(lib, pool, policy):
    """>= 3 candidates spanning several shape buckets: the vmapped engine
    matches per-candidate numpy runs to <= 1e-10 on queues / served /
    latency (and busy / realized), under both routing policies."""
    dag, alloc, vms, cands = pool
    maps = [c.mapping for c in cands]
    assert len(maps) >= 3
    omegas = np.linspace(60.0, 140.0, 5)
    sizes = []
    raw_v = evaluate_candidates(dag, alloc, maps, lib, omegas, policy=policy,
                                engine="vmap", bucket_sizes=sizes, **TINY)
    raw_n = evaluate_candidates(dag, alloc, maps, lib, omegas, policy=policy,
                                engine="numpy", **TINY)
    assert sum(sizes) == len(maps)
    for a, b in zip(raw_v, raw_n):
        for f in RAW_FIELDS:
            x, y = getattr(a, f), getattr(b, f)
            assert x.shape == y.shape, f
            if x.size:
                np.testing.assert_allclose(x, y, rtol=1e-10, atol=1e-10,
                                           err_msg=f)


def test_vmap_engine_matches_dataflow_simulator_scan(lib, pool):
    """A single-candidate 'bucket' agrees with the plain scan engine too
    (the vmapped kernel is the same tick body)."""
    dag, alloc, vms, cands = pool
    m = cands[0].mapping
    omegas = np.linspace(60.0, 140.0, 4)
    raw_v = evaluate_candidates(dag, alloc, [m], lib, omegas,
                                engine="vmap", **TINY)[0]
    sim = DataflowSimulator(dag, alloc, m, lib, cpu_penalty=True)
    raw_s = sim.sweep_raw(omegas, engine="scan", warmup=2.5, **TINY)
    for f in RAW_FIELDS:
        np.testing.assert_allclose(getattr(raw_v, f), getattr(raw_s, f),
                                   rtol=1e-10, atol=1e-10, err_msg=f)


def test_kernel_cache_hits_on_second_run(lib, pool):
    """A same-shape re-evaluation is a pure cache hit: no new kernel builds
    and no new jit compilations."""
    dag, alloc, vms, cands = pool
    maps = [c.mapping for c in cands]
    omegas = np.linspace(60.0, 140.0, 5)
    evaluate_candidates(dag, alloc, maps, lib, omegas, engine="vmap", **TINY)
    before = scan_kernel_cache_stats()
    evaluate_candidates(dag, alloc, maps, lib, omegas, engine="vmap", **TINY)
    after = scan_kernel_cache_stats()
    assert after["misses"] == before["misses"]
    assert after["compiled"] == before["compiled"]
    assert after["hits"] > before["hits"]


# -- the search never loses to a single mapper ---------------------------------

@pytest.mark.parametrize("policy", list(RoutingPolicy),
                         ids=[p.value for p in RoutingPolicy])
def test_search_not_worse_than_best_single_mapper(lib, policy):
    """Every §7 mapper is a candidate, so the ranked best's max stable rate
    is >= each single mapper's on the same pool and grid."""
    dag = linear_dag()
    ranked = search_mapping(dag, 100, lib, policy=policy, n_moves=2,
                            rate_fractions=np.linspace(0.6, 1.4, 7), **TINY)
    singles = [c for c in ranked.candidates if c.name in ("dsm", "rsm", "sam")]
    assert singles, "no base mapper fit the shared pool"
    for c in singles:
        assert ranked.best.max_stable_rate >= c.max_stable_rate - 1e-9


def test_plan_mapper_search_schedule_is_valid(lib):
    """``plan(mapper="search")`` returns an ordinary Schedule: every
    allocated thread mapped exactly once onto the pool, winner recorded."""
    s = plan(diamond_dag(), 100, lib, mapper="search",
             search_opts=dict(n_moves=2, rate_fractions=[0.8, 1.0, 1.2],
                              **TINY))
    assert s.mapper == "search"
    assert s.search_winner is not None
    assert set(s.mapping.assignment) == set(make_threads(s.allocation))
    pool_slots = {slot for vm in s.vms for slot in vm.slot_ids()}
    assert set(s.mapping.assignment.values()) <= pool_slots


def test_fleet_refine_search_never_hurts(lib):
    """Opt-in fleet refinement keeps the budgeted pools and only swaps a
    mapping in on a strict simulated win (base mapper is in the pool)."""
    dags = {"linear": linear_dag(), "diamond": diamond_dag()}
    opts = dict(n_moves=2, rate_fractions=[0.8, 1.0, 1.2], **TINY)
    stats = {}
    base = plan_fleet(dags, lib, budget_slots=10)
    fp = plan_fleet(dags, lib, budget_slots=10, refine_search=True,
                    search_opts=opts, stats=stats)
    assert stats["search_candidates"] > 0
    for name, e in fp.entries.items():
        assert e.omega == base.entries[name].omega     # rates untouched
        assert e.acquired_slots == base.entries[name].acquired_slots
        sched = e.schedule
        assert set(sched.mapping.assignment) == \
            set(make_threads(sched.allocation))


# -- candidate generation ------------------------------------------------------

def test_candidate_pool_is_deduped_and_complete(lib, pool):
    dag, alloc, vms, cands = pool
    threads = set(make_threads(alloc))
    sigs = [mapping_signature(c.mapping) for c in cands]
    assert len(set(sigs)) == len(sigs)
    names = [c.name for c in cands]
    assert len(set(names)) == len(names)
    assert "dsm" in names and "sam" in names
    for c in cands:
        assert set(c.mapping.assignment) == threads, c.name


def test_local_moves_preserve_group_shape(lib, pool):
    """Moves keep every (task, slot)-group size, so move candidates share
    the base's shape bucket (the vmap batching property)."""
    dag, alloc, vms, cands = pool
    base = next(c.mapping for c in cands if c.name == "sam")
    base_sizes = sorted(
        (t, q) for counts in base.slot_task_counts().values()
        for t, q in counts.items())
    moves = local_moves(base, n_moves=4, seed=1)
    assert moves
    for m in moves:
        sizes = sorted(
            (t, q) for counts in m.slot_task_counts().values()
            for t, q in counts.items())
        assert sizes == base_sizes
        assert set(m.assignment) == set(base.assignment)
        assert mapping_signature(m) != mapping_signature(base)


# -- warm-start hook (online controller's incumbent candidate) -----------------

def test_extra_candidates_warm_start(lib, pool):
    """An incumbent mapping passed via ``extra_candidates`` joins the pool
    under its own name (extras are added first, so dedup cannot fold it
    under a mapper's name), is evaluated and ranked, and the search result
    is never worse than the incumbent."""
    dag, alloc, vms, _ = pool
    from repro.core.mapping import map_sam
    incumbent = map_sam(dag, alloc, vms, lib)
    ranked = search_mapping(
        dag, 100, lib, allocation=alloc, vms=vms, grow_pool=False,
        n_moves=0, rate_fractions=[0.8, 1.2], duration=1.0, dt=0.5,
        extra_candidates={"incumbent": incumbent})
    inc = ranked.result_for("incumbent")
    assert inc is not None
    assert ranked.best.max_stable_rate >= inc.max_stable_rate
    assert ranked.gain_over("incumbent") is not None
    assert ranked.gain_over("incumbent") >= 0


def test_extra_candidates_validation(lib, pool):
    """Extras that do not map this allocation's thread set, or sit on VMs
    outside the search pool, are rejected up front."""
    dag, alloc, vms, _ = pool
    from repro.core.mapping import VM, map_dsm
    half = ALLOCATORS["mba"](dag, 50, lib)
    wrong_threads = map_dsm(dag, half, vms, lib)
    with pytest.raises(ValueError):
        search_mapping(dag, 100, lib, allocation=alloc, vms=vms,
                       grow_pool=False, n_moves=0, rate_fractions=[1.0],
                       duration=1.0, dt=0.5,
                       extra_candidates={"bad": wrong_threads})
    foreign = [VM(900 + i, vm.num_slots) for i, vm in enumerate(vms)]
    off_pool = map_dsm(dag, alloc, foreign, lib)
    with pytest.raises(ValueError):
        search_mapping(dag, 100, lib, allocation=alloc, vms=vms,
                       grow_pool=False, n_moves=0, rate_fractions=[1.0],
                       duration=1.0, dt=0.5,
                       extra_candidates={"bad": off_pool})

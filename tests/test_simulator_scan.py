"""The jitted ``lax.scan`` sweep engine vs the numpy reference engine.

The contract is *equivalence*: the scan kernel must reproduce the numpy tick
loop to <= 1e-10 on every raw surface (queues, served, realized rates,
latency series, slot busy time) — for every DAG of ``ALL_DAGS``, per routing
policy, and through the fleet co-simulation path — while running the whole
time loop inside one XLA program.  The measurement satellites are pinned
here too: the stability slope is per *second* (verdicts invariant to
``latency_sample_every``), ``slot_busy`` covers exactly the post-warmup
window of the realized horizon, and the short-run tail window is explicit.
"""

import numpy as np
import pytest

from repro.core import (ALL_DAGS, DataflowSimulator, RoutingPolicy,
                        SweepBatch, linear_dag, paper_library, plan,
                        plan_fleet, simulate_fleet)
from repro.core.predictor import effective_capacity_matrix

RAW_FIELDS = ("queues", "busy", "served", "realized", "latency")
EVERY_DAG = pytest.mark.parametrize("mk", list(ALL_DAGS.values()),
                                    ids=list(ALL_DAGS))
POLICIES = pytest.mark.parametrize("policy", list(RoutingPolicy),
                                   ids=[p.value for p in RoutingPolicy])


@pytest.fixture(scope="module")
def lib():
    return paper_library()


def _sim(lib, mk=linear_dag, policy=RoutingPolicy.SHUFFLE, **kw):
    dag = mk()
    s = plan(dag, 100, lib, allocator="mba", mapper="sam")
    return DataflowSimulator(dag, s.allocation, s.mapping, lib,
                             policy=policy, **kw)


def _assert_raw_close(a, b, tol=1e-10):
    for f in RAW_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.shape == y.shape, f
        if x.size:
            np.testing.assert_allclose(x, y, rtol=tol, atol=tol,
                                       err_msg=f)


# -- engine equivalence --------------------------------------------------------

@EVERY_DAG
@POLICIES
def test_scan_matches_numpy_raw(lib, policy, mk):
    """Raw state (queues, served, realized, latency, busy) matches to 1e-10
    across a sweep spanning stable and overloaded rates, and the derived
    SimResults agree field by field."""
    sim = _sim(lib, mk, policy=policy)
    omegas = np.linspace(20.0, 180.0, 13)
    kw = dict(duration=8.0, dt=0.1)
    _assert_raw_close(sim.sweep_raw(omegas, engine="numpy", **kw),
                      sim.sweep_raw(omegas, engine="scan", **kw))
    for a, b in zip(sim.simulate_sweep(omegas, engine="numpy", **kw),
                    sim.simulate_sweep(omegas, engine="scan", **kw)):
        assert a.stable == b.stable
        assert a.latency_slope == pytest.approx(b.latency_slope, abs=1e-10)
        assert a.mean_latency == pytest.approx(b.mean_latency, abs=1e-10)
        assert a.p99_latency == pytest.approx(b.p99_latency, abs=1e-10)
        assert a.queue_total == pytest.approx(b.queue_total, rel=1e-10,
                                              abs=1e-10)
        assert a.slot_busy.keys() == b.slot_busy.keys()
        for slot, busy in a.slot_busy.items():
            assert b.slot_busy[slot] == pytest.approx(busy, abs=1e-10)


def test_scan_run_is_the_k1_column(lib):
    """``run(engine="scan")`` equals the numpy single-rate run."""
    sim = _sim(lib)
    a = sim.run(90.0, duration=6.0, dt=0.1, engine="numpy")
    b = sim.run(90.0, duration=6.0, dt=0.1, engine="scan")
    assert a.stable == b.stable
    assert b.latency_slope == pytest.approx(a.latency_slope, abs=1e-10)
    np.testing.assert_allclose(b.latency_samples, a.latency_samples,
                               rtol=1e-10, atol=1e-10)
    for slot, busy in a.slot_busy.items():
        assert b.slot_busy[slot] == pytest.approx(busy, abs=1e-10)


#: Fleets of ``ALL_DAGS`` with a slot budget their plan fits.
FLEETS = {
    "linear+diamond": (("linear", "diamond"), 12),
    "traffic+finance+grid": (("traffic", "finance", "grid"), 24),
    "all": (tuple(ALL_DAGS), 36),
}


@pytest.mark.parametrize("fleet", list(FLEETS))
@POLICIES
def test_fleet_cosim_scan_matches_numpy(lib, policy, fleet):
    """Acceptance: a fleet co-simulated through one batched scan call
    matches the numpy engine to <= 1e-10, under both routing policies."""
    names, budget = FLEETS[fleet]
    fp = plan_fleet({n: ALL_DAGS[n]() for n in names}, lib,
                    budget_slots=budget)
    kw = dict(duration=8.0, dt=0.1, policy=policy)
    rep_n = simulate_fleet(fp, lib, engine="numpy", **kw)
    rep_s = simulate_fleet(fp, lib, engine="scan", **kw)
    assert rep_n.entries.keys() == rep_s.entries.keys()
    for name in rep_n.entries:
        a, b = rep_n.entries[name], rep_s.entries[name]
        assert a.actual_max_stable == b.actual_max_stable
        assert a.predicted_max_rate == b.predicted_max_rate
        for ra, rb in zip(a.results, b.results):
            assert ra.stable == rb.stable
            assert rb.latency_slope == pytest.approx(ra.latency_slope,
                                                     abs=1e-10)
            np.testing.assert_allclose(rb.latency_samples,
                                       ra.latency_samples,
                                       rtol=1e-10, atol=1e-10)
    assert rep_n.slot_busy.keys() == rep_s.slot_busy.keys()
    for slot, busy in rep_n.slot_busy.items():
        assert rep_s.slot_busy[slot] == pytest.approx(busy, abs=1e-10)
    for vm, cpu in rep_n.vm_cpu_actual.items():
        assert rep_s.vm_cpu_actual[vm] == pytest.approx(cpu, abs=1e-10)
    for vm, mem in rep_n.vm_mem_actual.items():
        assert rep_s.vm_mem_actual[vm] == pytest.approx(mem, abs=1e-10)


def test_cosim_busy_adds_on_shared_slots(lib):
    """Two dataflows co-simulated on the SAME mapping accumulate busy time
    on the shared slots additively (the shared-VM-pool semantics)."""
    sims = [_sim(lib), _sim(lib)]
    kw = dict(duration=4.0, dt=0.1)
    solo = sims[0].sweep_raw([50.0], engine="numpy", **kw)
    both = SweepBatch(sims).sweep_raw([[50.0], [50.0]], engine="numpy", **kw)
    assert len(both.busy) == len(solo.busy)      # slots deduplicated
    np.testing.assert_allclose(both.busy, 2 * solo.busy, rtol=1e-12)


@EVERY_DAG
def test_max_stable_rate_engines_agree(lib, mk):
    sim = _sim(lib, mk)
    r_np = sim.max_stable_rate(duration=8.0, dt=0.1, engine="numpy")
    r_sc = sim.max_stable_rate(duration=8.0, dt=0.1, engine="scan")
    assert r_sc == pytest.approx(r_np, rel=0.02)
    assert r_np > 0


# -- stability-slope units (per second, not per sample) ------------------------

def test_verdicts_invariant_to_latency_sample_interval(lib):
    """Halving ``latency_sample_every`` must not change stable/unstable
    verdicts: the slope criterion is seconds of latency per second of run
    time, not per sample."""
    sim = _sim(lib)
    omegas = np.linspace(20.0, 200.0, 10)
    kw = dict(duration=10.0, dt=0.05)
    coarse = sim.simulate_sweep(omegas, latency_sample_every=0.25, **kw)
    fine = sim.simulate_sweep(omegas, latency_sample_every=0.125, **kw)
    assert [r.stable for r in coarse] == [r.stable for r in fine]
    # the per-second slopes themselves agree (same fitted trend, different
    # sampling of the same deterministic latency curve)
    for a, b in zip(coarse, fine):
        assert b.latency_slope == pytest.approx(a.latency_slope,
                                                rel=0.05, abs=1e-6)


# -- slot_busy window: realized horizon, warmup excluded -----------------------

def test_slot_busy_is_analytic_utilization_on_nonintegral_horizon(lib):
    """With duration/dt non-integral (realized horizon != duration), busy
    fractions still equal the exact fluid utilization sum(arr_g/cap_g) per
    slot — i.e. they are normalized by the realized post-warmup window, not
    the requested duration."""
    sim = _sim(lib)
    gi = sim.gi
    omega = 60.0
    res = sim.run(omega, duration=10.02, dt=0.05, warmup=5.0)
    caps = effective_capacity_matrix(gi, np.array([omega]),
                                     cpu_penalty=sim.cpu_penalty)[:, 0]
    arr = gi.g_frac * gi.betas[gi.g_task] * omega
    expected = {}
    for g in range(gi.n_groups):
        s = gi.slots[int(gi.g_slot[g])]
        util = min(arr[g], caps[g]) / caps[g] if caps[g] > 0 else 0.0
        expected[s] = expected.get(s, 0.0) + util
    assert res.slot_busy.keys() == expected.keys()
    for slot, want in expected.items():
        assert res.slot_busy[slot] == pytest.approx(want, abs=1e-9)


def test_slot_busy_saturates_exactly(lib):
    """A deeply overloaded schedule pegs its bottleneck groups at exactly
    1.0 busy over the measured window (a non-integral undershoot means
    warmup ticks or the requested-but-unrealized duration leaked into the
    normalization)."""
    sim = _sim(lib)
    gi = sim.gi
    res = sim.run(500.0, duration=10.02, dt=0.05, warmup=5.0)
    assert not res.stable
    # a saturated group contributes exactly 1.0: some slot must sit at an
    # integral busy value; under the old ``/duration`` normalization every
    # saturated slot would read steps*dt/duration = 10.0/10.02 ~ 0.998
    saturated = [b for b in res.slot_busy.values()
                 if abs(b - round(b)) < 1e-9 and b >= 1.0 - 1e-9]
    assert saturated, res.slot_busy


# -- explicit short-run tail window --------------------------------------------

def test_short_run_uses_whole_series_and_reports_it(lib):
    """A run shorter than warmup has no post-warmup samples: the WHOLE
    series is judged and ``latency_samples`` reports exactly that window."""
    sim = _sim(lib)
    res = sim.run(50.0, duration=2.0, dt=0.1, warmup=5.0)
    # steps=20, sample every 2 ticks -> 10 samples, all pre-warmup
    assert len(res.latency_samples) == 10
    assert res.mean_latency == pytest.approx(np.mean(res.latency_samples))


def test_tail_window_boundary_is_explicit(lib):
    """>= 3 post-warmup samples: only they are judged; 1-2 post-warmup
    samples: fall back to the whole series.  ``latency_samples`` always
    equals the judged window."""
    sim = _sim(lib)
    # dt=0.1, sample every 2 ticks -> samples at t = 0.0, 0.2, ...
    long = sim.run(50.0, duration=5.6, dt=0.1, warmup=5.0)
    assert len(long.latency_samples) == 3          # t = 5.0, 5.2, 5.4
    short = sim.run(50.0, duration=5.4, dt=0.1, warmup=5.0)
    assert len(short.latency_samples) == 27        # whole series: t<=5.2
    assert short.mean_latency == pytest.approx(np.mean(short.latency_samples))

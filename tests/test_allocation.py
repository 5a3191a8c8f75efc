"""LSA (Alg. 2) and MBA (Alg. 3) allocation, and both against a plain
reference written from the algorithms."""

import math

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:        # property tests skip; plain tests still run
    from _hypothesis_fallback import hypothesis, st
import pytest

from repro.core import (ALL_DAGS, MICRO_DAGS, ModelLibrary, PAPER_MODELS,
                        PerfModel, UnsupportableRateError, allocate_lsa,
                        allocate_mba, linear_dag, paper_library)
from repro.core.dag import Dataflow


@pytest.fixture(scope="module")
def lib():
    return paper_library()


def dead_task_setup():
    """A task whose profile supports no rate at all: every positive rate is
    unsupportable for both allocators."""
    models = ModelLibrary({
        "dead": PerfModel.from_points("dead", {1: (0.0, 0.5, 0.5)}),
        "source": PAPER_MODELS["source"],
        "sink": PAPER_MODELS["sink"],
    })
    df = Dataflow("deadflow")
    df.add_task("src", "source", is_source=True)
    df.add_task("d", "dead")
    df.add_task("snk", "sink", is_sink=True)
    df.add_edge("src", "d")
    df.add_edge("d", "snk")
    return df, models


@pytest.mark.parametrize("allocate", [allocate_lsa, allocate_mba])
def test_unsupportable_rate_raises_typed_error(allocate):
    """Not a bare assert (silently skipped under python -O) — a typed
    RuntimeError planners can catch, like the mapper's
    InsufficientResourcesError."""
    dag, models = dead_task_setup()
    with pytest.raises(UnsupportableRateError) as exc:
        allocate(dag, 50.0, models)
    assert isinstance(exc.value, RuntimeError)
    assert not isinstance(exc.value, AssertionError)
    assert exc.value.task == "d"
    assert exc.value.rate == pytest.approx(50.0)


def test_lsa_blob_paper_numbers(lib):
    """§8.4.1: LSA gives the Blob task 50 threads with 337% CPU and 1196%
    memory for the Linear DAG at 100 t/s."""
    alloc = allocate_lsa(linear_dag(), 100.0, lib)
    blob = alloc.tasks["b"]
    assert blob.threads == 50                       # ceil(100 / 2.0)
    assert blob.cpu * 100 == pytest.approx(337, rel=0.05)
    assert blob.mem * 100 == pytest.approx(1196, rel=0.01)


def test_mba_blob_bundles(lib):
    """MBA packs full bundles of 50 threads at the 30 t/s operating point."""
    alloc = allocate_mba(linear_dag(), 100.0, lib)
    blob = alloc.tasks["b"]
    assert blob.bundle_size == 50
    assert blob.full_bundles == 3                   # 3 x 30 = 90 of 100 t/s
    assert blob.threads > 150                       # + residual threads
    # full bundles charged a whole slot each
    assert blob.cpu >= 3.0 and blob.mem >= 3.0


def test_static_source_sink(lib):
    alloc = allocate_mba(linear_dag(), 100.0, lib)
    assert alloc.tasks["src"].threads == 1
    assert alloc.tasks["src"].cpu == pytest.approx(0.10)
    assert alloc.tasks["src"].mem == pytest.approx(0.15)
    assert alloc.tasks["snk"].mem == pytest.approx(0.20)


@pytest.mark.parametrize("dag_name", list(MICRO_DAGS))
@pytest.mark.parametrize("omega", [50, 100, 200])
def test_lsa_allocates_more_slots_than_mba(lib, dag_name, omega):
    """Fig. 7's headline: LSA's linear extrapolation over-allocates ~2x."""
    dag = MICRO_DAGS[dag_name]()
    lsa = allocate_lsa(dag, omega, lib)
    mba = allocate_mba(dag, omega, lib)
    assert lsa.slots >= mba.slots
    assert lsa.slots >= 1.5 * mba.slots             # paper: ~2x


@pytest.mark.parametrize("dag_name", list(MICRO_DAGS))
def test_mba_allocates_more_threads(lib, dag_name):
    """§8.4.1: MBA allocates ~3x more threads (cheap) for fewer slots."""
    dag = MICRO_DAGS[dag_name]()
    lsa = allocate_lsa(dag, 100, lib)
    mba = allocate_mba(dag, 100, lib)
    assert mba.total_threads > 2 * lsa.total_threads


@hypothesis.given(omega=st.floats(min_value=5, max_value=500),
                  dag_name=st.sampled_from(sorted(ALL_DAGS)))
@hypothesis.settings(max_examples=40, deadline=None)
def test_allocation_invariants(omega, dag_name):
    """Every task gets >= 1 thread; resources are positive and finite;
    slot estimate covers both CPU and memory totals."""
    lib = paper_library()
    dag = ALL_DAGS[dag_name]()
    for alloc in (allocate_lsa(dag, omega, lib), allocate_mba(dag, omega, lib)):
        for name, ta in alloc.tasks.items():
            assert ta.threads >= 1
            assert 0 <= ta.cpu < 1e4 and 0 <= ta.mem < 1e4
        assert alloc.slots >= alloc.total_cpu - 1
        assert alloc.slots >= alloc.total_mem - 1


@hypothesis.given(omega=st.floats(min_value=5, max_value=300))
@hypothesis.settings(max_examples=30, deadline=None)
def test_allocation_monotone_in_rate(omega):
    """More input rate never needs fewer slots."""
    lib = paper_library()
    dag = linear_dag()
    a1 = allocate_mba(dag, omega, lib)
    a2 = allocate_mba(dag, omega * 2, lib)
    assert a2.slots >= a1.slots
    assert a2.total_threads >= a1.total_threads


# -- a plain reference, straight from Alg. 2 and Alg. 3 -----------------------

def _covering_threads(model, rate):
    """The smallest thread count ``q >= 1`` whose ``I(q)`` covers ``rate``,
    by a plain walk up the integers (None past the last measured count)."""
    for q in range(1, model.points[-1].tau + 1):
        if model.I(q) >= rate:
            return q
    return None


def _reference_task(model, algorithm, rate):
    """One task's (threads, cpu, mem) as Alg. 2 (LSA) or Alg. 3 (MBA) has
    it, from the profile's ``I``/``C``/``M`` alone."""
    if model.static:                # §8.3: source and sink, one thread
        return 1, model.C(1), model.M(1)
    if algorithm == "lsa":
        # one thread per omega_bar of rate; the residual thread's
        # resources scaled from one thread's
        omega_bar = model.I(1)
        full = math.floor(rate / omega_bar)
        resid = rate - full * omega_bar
        threads, cpu, mem = full, full * model.C(1), full * model.M(1)
        if resid > 0:
            threads += 1
            cpu += model.C(1) * resid / omega_bar
            mem += model.M(1) * resid / omega_bar
        return threads, cpu, mem
    # MBA: full bundles of tau_hat threads at omega_hat, a whole slot each;
    # the residual on the fewest threads that cover it
    omega_hat = max(p.rate for p in model.points)
    tau_hat = _covering_threads(model, omega_hat)
    bundles = math.floor(rate / omega_hat)
    resid = rate - bundles * omega_hat
    threads, cpu, mem = bundles * tau_hat, float(bundles), float(bundles)
    if resid > 0:
        q = _covering_threads(model, resid)
        assert q is not None
        threads += q
        if q == 1:
            cpu += model.C(1) * resid / model.I(1)
            mem += model.M(1) * resid / model.I(1)
        else:
            cpu += model.C(q)
            mem += model.M(q)
    return threads, cpu, mem


@pytest.mark.parametrize("omega", [50, 100, 200])
@pytest.mark.parametrize("algorithm", ["lsa", "mba"])
@pytest.mark.parametrize("dag_name", list(ALL_DAGS))
def test_allocation_matches_the_plain_reference(lib, dag_name, algorithm,
                                                omega):
    """Every task's thread count, CPU and memory equal Alg. 2 / Alg. 3
    written out by hand, at the Fig. 7 rates."""
    dag = ALL_DAGS[dag_name]()
    allocate = {"lsa": allocate_lsa, "mba": allocate_mba}[algorithm]
    alloc = allocate(dag, omega, lib)
    rates = dag.get_rates(omega)
    assert set(alloc.tasks) == set(rates)
    for name, ta in alloc.tasks.items():
        threads, cpu, mem = _reference_task(lib[ta.kind], algorithm,
                                            rates[name])
        assert ta.threads == threads, name
        assert ta.cpu == pytest.approx(cpu, rel=1e-12, abs=1e-12), name
        assert ta.mem == pytest.approx(mem, rel=1e-12, abs=1e-12), name

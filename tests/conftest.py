"""Shared fixtures.  NOTE: no XLA_FLAGS here on purpose — the tests and
benches must see the real single CPU device."""

import pytest

from repro.core import paper_library, set_default_validate


@pytest.fixture(scope="session", autouse=True)
def _validate_all_plans():
    """Turn the repro.analysis verifier on for every planner call in the
    suite: any test that builds an internally inconsistent Schedule /
    FleetPlan / controller state fails loudly instead of silently passing
    (and the verifier itself is proven false-positive-free on every
    artifact the suite constructs)."""
    prev = set_default_validate(True)
    yield
    set_default_validate(prev)


@pytest.fixture(scope="session")
def lib():
    return paper_library()

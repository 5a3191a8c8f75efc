"""Each cell's control fails the cell's limits while the program passes
them: the reference one precision below what the configuration states
stands in for the program (the test-size form of ``readings.py``)."""

import jax
import pytest

import run


@pytest.mark.parametrize("workload", ["fig7.cosim", "traffic.search",
                                      "traffic.stream"])
def test_control_fails_and_program_passes(workload):
    _, cell, cfg, traffic, limits = run.cell_files(workload)
    load = run.load_class(traffic["load"])(
        cfg, traffic, 2 ** 32 + 17, jax.devices()[:int(cell["chips"])],
        limits)
    load.setup(1.5)
    load.run(1.5)
    load.release()
    assert load.items
    assert all(c.ok for c in load.check())
    assert not all(c.ok for c in load.check(control=True))

"""Every cell of ``BENCHMARK.json`` finds its configuration, traffic mix,
load, limits and metric readers by name, each in a file of its own."""

import pytest

import run

BENCH = run.read_json(run.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_a_cell_finds_its_files_by_name(workload):
    _, cell, cfg, traffic, limits = run.cell_files(workload)
    assert cfg["name"] == cell["config"]
    assert callable(run.load_class(traffic["load"]))
    assert limits and all(v >= 0 for v in limits.values())
    for traced in (False, True):
        metrics = run.cell_metrics(BENCH, workload, traced)
        assert metrics
        for m in metrics:
            assert callable(run.load_reader(m["name"]))
    names = {m["name"] for m in run.cell_metrics(BENCH, workload, False)}
    assert "setup_s" in names and len(names) >= 2


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        run.cell_files("no.such.cell")

import numpy as np
import pytest

import deploy
import roofline
import run


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_scan_work_grows_with_ticks_and_the_bound_is_named():
    cfg = run.cell_files("traffic.search")[2]
    from repro.core import plan
    sched = plan(deploy.dataflow(cfg, "traffic"), cfg["rate"], deploy.library(cfg),
                 allocator="mba", mapper="sam", vm_sizes="azure-d")
    facts = [deploy.dag_facts(cfg, "traffic", "t", sched.mapping,
                              cfg["rate"] * np.linspace(0.25, 1.25, 9))]
    short = roofline.scan_work(facts, cfg["profiles"], duration=8.0, dt=0.1,
                               sample_every=0.25)
    long = roofline.scan_work(facts, cfg["profiles"], duration=16.0, dt=0.1,
                              sample_every=0.25)
    assert long["ops"] > 1.9 * short["ops"] > 0
    assert long["bytes"] > short["bytes"] > 0
    peak = roofline.peaks("TPU v5 lite")
    t = roofline.least_time(short, peak)
    assert t["bound"] == "bytes"
    assert t["seconds"] == pytest.approx(short["bytes"] / 819e9)

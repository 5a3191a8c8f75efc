"""The plain references against the program on the CPU, where both are
exact enough to agree far inside the limits."""

import jax
import numpy as np
import pytest

import deploy
import generator
import run
from reference import operators as ref_ops
from reference import ticks as ref_ticks

FLEET = run.cell_files("fig7.cosim")[2]
TRAFFIC = run.cell_files("traffic.stream")[2]
LIMITS = run.cell_files("traffic.stream")[4]


def test_cut_follows_thread_shares_in_slot_order():
    groups = {(1, 0): 3, (0, 2): 2, (0, 1): 2}
    parts = ref_ops.cut(groups, 16)
    assert [p[0] for p in parts] == [(0, 1), (0, 2), (1, 0)]
    assert [p[2] - p[1] for p in parts] == [5, 4, 7]
    assert parts[-1][2] == 16


@pytest.mark.parametrize("dag_type", ["linear", "diamond", "star",
                                      "traffic"])
def test_operator_reference_matches_the_executor(dag_type):
    from repro.core import plan
    from repro.runtime import StreamExecutor, VirtualClock
    from repro.runtime.stream import MicroBatch
    cfg = TRAFFIC if dag_type == "traffic" else FLEET
    lib = deploy.library(cfg)
    sched = plan(deploy.dataflow(cfg, dag_type), 150.0, lib,
                 allocator="mba", mapper="sam", vm_sizes="azure-d")
    ex = StreamExecutor(sched, lib, clock=VirtualClock(),
                        devices=jax.devices()[:1])
    spec = cfg["dags"][dag_type]
    tasks = {t[0]: t[1] for t in spec["tasks"]}
    edges = [(e[0], e[1]) for e in spec["edges"]]
    for seq in range(3):
        frame = generator.frame_payload(2 ** 33 + 5, seq, 16, 256)
        status, _ = ex.process_frame(MicroBatch(seq, frame, 0.0), 0.0)
        assert status == "ok"
        got = {s: {k: np.asarray(v) for k, v in o.items()}
               for s, o in ex.last_sink_outputs.items()}
        want = ref_ops.run_frame(tasks, edges,
                                 deploy.mapping_groups(sched.mapping), frame)
        bad, err = ref_ops.compare(got, want)
        assert bad == 0 and err <= LIMITS["sink_float_err"]
        if dag_type == "linear":
            # the split reaches the sink here: one group per task differs
            whole = ref_ops.run_frame(
                tasks, edges, {t: {(0, 0): 1} for t in tasks}, frame)
            assert ref_ops.compare(whole, want) != (0, 0.0)


def test_tick_reference_matches_the_numpy_engine():
    cfg = FLEET
    lib = deploy.library(cfg)
    ctl = deploy.controller(cfg, lib)
    for entry in cfg["script"][:4]:
        ctl.apply(deploy.script_event(cfg, ctl, entry))
    fr = np.linspace(0.3, 1.2, 5)
    rep = ctl.cosimulate(fractions=fr, engine="numpy")
    types = deploy.dag_types(cfg)
    facts = [deploy.dag_facts(cfg, types[n], n, ctl.entry(n).schedule.mapping,
                              rep.entries[n].omegas) for n in rep.entries]
    res = ref_ticks.simulate(facts, cfg["profiles"], duration=8.0, dt=0.1,
                             warmup=2.0, sample_every=0.25)
    for name, r in zip(rep.entries, res):
        e = rep.entries[name]
        got = np.array([x.latency_samples for x in e.results]).T
        assert np.allclose(got, r.latency_samples, rtol=1e-12, atol=1e-15)
        assert np.array_equal([x.stable for x in e.results], r.stable)
        assert np.allclose([x.queue_total for x in e.results],
                           r.queue_total, rtol=1e-12, atol=1e-9)
        for s, v in r.slot_busy.items():
            from repro.core.mapping import SlotId
            busy = [x.slot_busy[SlotId(*s)] for x in e.results]
            assert np.allclose(busy, v, rtol=1e-12, atol=1e-15)

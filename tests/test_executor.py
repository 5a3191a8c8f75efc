"""The executor's route: a frame cut over a task's slot groups, and the
parts' outputs interleaved back, each in one compiled program, against the
eager per-key cut and concatenation they replace.  And its dispatch: every
part launched without a wait, against a wait after every part, with one
host block per sink and the busy time calibration reads."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import ALL_DAGS, linear_dag, plan, traffic_dag
from repro.core.perfmodel import PerfModel
from repro.runtime import StreamExecutor, VirtualClock
from repro.runtime import executor as executor_mod
from repro.runtime.stream import MicroBatch

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.append(str(BENCH))

import deploy  # noqa: E402
from loads.keyed_stream import sys_frame  # noqa: E402

ROUTE = "repro_executor_route_launches_total"


@pytest.fixture
def metrics():
    obs.REGISTRY.reset()
    obs.REGISTRY.enable()
    yield
    obs.REGISTRY.disable()
    obs.REGISTRY.reset()


def _launches(stage):
    return obs.snapshot()[f'{ROUTE}{{stage="{stage}"}}']["value"]


def _frame(n, seed=0, keys=("payload", "value", "tags", "checksum", "pi")):
    """A frame of ``n`` tuples with the keys a multi-slot task of the
    Traffic app reads, as host arrays."""
    rng = np.random.default_rng([seed, n])
    full = {"payload": rng.integers(32, 127, (n, 256), dtype=np.uint8),
            "value": rng.random(n, dtype=np.float32),
            "tags": rng.integers(0, 9, n, dtype=np.int32),
            "checksum": rng.integers(0, 2 ** 20, n, dtype=np.uint32),
            "pi": rng.random(n, dtype=np.float32)}
    return {k: full[k] for k in keys}


def _on_device(frame):
    return {k: jnp.asarray(v) for k, v in frame.items()}


def _assert_same(got, want):
    # a compiled program returns a dict's keys sorted
    assert sorted(got) == sorted(want)
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype and g.shape == w.shape, k
        assert g.tobytes() == w.tobytes(), k


def _eager_split(arrays, bounds):
    return [{k: v[lo:hi] for k, v in arrays.items()} for lo, hi in bounds]


def _eager_interleave(outs):
    home = next(iter(outs[0].values())).devices().pop()
    return {k: jnp.concatenate([jax.device_put(o[k], home) for o in outs],
                               axis=0)
            for k in outs[0]}


CUTS = pytest.mark.parametrize("n, bounds", [
    (16, ((0, 6), (6, 16))),
    (16, ((0, 5), (5, 10), (10, 15), (15, 16))),
    (16, ((0, 0), (0, 7), (7, 16))),
    (8, ((0, 3), (3, 8))),
    (8, ((0, 2), (2, 5), (5, 8), (8, 8))),
], ids=["threads-38-60", "threads-50-50-50-10", "zero-width-part",
        "half-frame-38-60", "half-frame-50-50-50-10"])


@CUTS
def test_split_gives_the_eager_parts(n, bounds):
    arrays = _on_device(_frame(n))
    got = executor_mod._split(arrays, bounds=bounds)
    want = _eager_split(arrays, bounds)
    assert len(got) == len(want) == len(bounds)
    for g, w, (lo, hi) in zip(got, want, bounds):
        _assert_same(g, w)
        assert next(iter(g.values())).shape[0] == hi - lo


@CUTS
def test_interleave_gives_the_eager_concatenation(n, bounds):
    arrays = _on_device(_frame(n))
    # a per-part output, so the order of the parts shows in the result
    outs = [{**p, "digest": jnp.cumsum(p["value"])}
            for p in _eager_split(arrays, bounds)]
    got = executor_mod._interleave(outs)
    _assert_same(got, _eager_interleave(outs))
    _assert_same({k: got[k] for k in arrays}, arrays)


#: Every DAG of ``ALL_DAGS`` at 100 tuples/s, the linear one at 120.
RATES = {name: 120 if name == "linear" else 100 for name in ALL_DAGS}
EVERY_DAG = pytest.mark.parametrize(
    "dag, rate", [(ALL_DAGS[name], rate) for name, rate in RATES.items()],
    ids=[f"{name}-{rate}" for name, rate in RATES.items()])


def _executor(lib, dag=traffic_dag, rate=100):
    schedule = plan(dag(), rate, lib, allocator="mba", mapper="sam",
                    vm_sizes="azure-d")
    return StreamExecutor(schedule, lib, clock=VirtualClock(),
                          devices=jax.devices()[:1])


def _outputs(ex, frames, on_device):
    """Every task's output of every frame, in the order the tasks ran."""
    seen = []
    run_task = ex._run_task

    def recording(task, arrays, *args):
        out = run_task(task, arrays, *args)
        seen.append((task, {k: np.asarray(v) for k, v in out.items()}))
        return out
    ex._run_task = recording
    for seq, frame in enumerate(frames):
        arrays = _on_device(frame) if on_device else frame
        status, _ = ex.process_frame(MicroBatch(seq, arrays, 0.0), 0.0)
        assert status == "ok"
    return seen


@pytest.mark.parametrize("n", [16, 8], ids=["frame", "half-frame"])
@pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
@EVERY_DAG
def test_task_outputs_match_the_eager_route(lib, monkeypatch, dag, rate, n,
                                            on_device):
    frames = [_frame(n, seed, keys=("payload", "value"))
              for seed in (11, 12, 13)]
    compiled = _outputs(_executor(lib, dag, rate), frames, on_device)
    monkeypatch.setattr(executor_mod, "_split", _eager_split)
    monkeypatch.setattr(executor_mod, "_interleave", _eager_interleave)
    eager = _outputs(_executor(lib, dag, rate), frames, on_device)
    assert [t for t, _ in compiled] == [t for t, _ in eager]
    for (_, got), (_, want) in zip(compiled, eager):
        _assert_same(got, want)


@pytest.mark.parametrize("dag, rate, per_frame", [
    (traffic_dag, 100, 2), (linear_dag, 10, 0), (linear_dag, 120, 3)],
    ids=["traffic-100", "linear-10", "linear-120"])
def test_route_launches_one_split_and_interleave_per_multi_part_task(
        lib, metrics, dag, rate, per_frame):
    ex = _executor(lib, dag, rate)
    assert sum(len(g) > 1 for g in ex.groups.values()) == per_frame
    frames = [_frame(16, seed, keys=("payload", "value"))
              for seed in range(3)]
    _outputs(ex, frames, on_device=True)
    assert _launches("split") == _launches("interleave") == 3 * per_frame


def test_host_frame_at_a_multi_slot_task_is_cut_on_the_host(lib, metrics):
    ex = _executor(lib)
    assert len(ex.groups["lookup"]) > 1
    host = _frame(16, 5)
    out = ex._run_task("lookup", host)
    assert _launches("split") == 0
    assert _launches("interleave") == 1
    _assert_same(out, ex._run_task("lookup", _on_device(host)))
    assert _launches("split") == 1


# -- dispatch without a wait per part -----------------------------------------

WAITS = "repro_executor_host_waits_total"


def _waited(monkeypatch):
    """Every part's output waited for as it returns, as the executor once
    did."""
    invoke = StreamExecutor._invoke_part

    def waited(self, *args, **kwargs):
        out = invoke(self, *args, **kwargs)
        return out if out is None else jax.block_until_ready(out)
    monkeypatch.setattr(StreamExecutor, "_invoke_part", waited)


def _sinks(ex, frames, on_device):
    """Every frame's sink outputs, on the host."""
    seen = []
    for seq, frame in enumerate(frames):
        arrays = _on_device(frame) if on_device else frame
        status, _ = ex.process_frame(MicroBatch(seq, arrays, 0.0), 0.0)
        assert status == "ok"
        seen.append({s: {k: np.asarray(v) for k, v in out.items()}
                     for s, out in ex.last_sink_outputs.items()})
    return seen


@pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
@EVERY_DAG
def test_sinks_match_a_wait_after_every_part(lib, monkeypatch, dag, rate,
                                            on_device):
    frames = [_frame(16, seed, keys=("payload", "value"))
              for seed in (21, 22, 23)]
    launched = _sinks(_executor(lib, dag, rate), frames, on_device)
    _waited(monkeypatch)
    waited = _sinks(_executor(lib, dag, rate), frames, on_device)
    assert len(launched) == len(waited) == len(frames)
    for got, want in zip(launched, waited):
        assert sorted(got) == sorted(want) and got
        for sink in want:
            _assert_same(got[sink], want[sink])


@pytest.mark.parametrize("dag, rate", [(traffic_dag, 100), (linear_dag, 120)],
                         ids=["traffic-100", "linear-120"])
def test_a_frame_blocks_once_per_sink(lib, metrics, monkeypatch, dag, rate):
    ex = _executor(lib, dag, rate)
    sinks = len(ex.dag.sinks())
    assert sum(len(g) for g in ex.groups.values()) > sinks
    blocks = []
    block = jax.block_until_ready

    def counting(x):
        blocks.append(x)
        return block(x)
    monkeypatch.setattr(executor_mod.jax, "block_until_ready", counting)
    frames = [_frame(16, seed, keys=("payload", "value"))
              for seed in range(3)]
    _sinks(ex, frames, on_device=True)
    assert len(blocks) == 3 * sinks
    assert obs.snapshot()[WAITS]["value"] == 3 * sinks


#: ``measurements()`` of the Traffic app at 100 tuples/s on the virtual
#: clock, 8 frames of 16 tuples (seed 5) under a slowdown, a retried error
#: and a stall that times the last frame out, as the executor read them
#: when it waited for every part: (task, threads, tuples, busy seconds)
VIRTUAL_MEASUREMENTS = [
    ("agg", 1, 112.0, 0.001866666666666667),
    ("archive", 1, 128.0, 0.002133333333333334),
    ("filter", 1, 128.0, 1.2190476190476192),
    ("lookup", 38, 48.0, 1.7999999999999994),
    ("lookup", 60, 80.0, 2.0),
    ("model", 50, 35.0, 1.1666666666666665),
    ("model", 50, 35.0, 1.1666666666666665),
    ("model", 50, 35.0, 1.1666666666666665),
    ("model", 10, 7.0, 0.7),
    ("parse", 1, 128.0, 0.41290322580645167),
    ("snk", 1, 112.0, 0.00011199999999999998),
    ("speed", 1, 128.0, 1.2190476190476192),
    ("src", 1, 128.0, 0.000128),
]


def test_virtual_clock_measurements_are_unchanged(lib):
    from repro.runtime import FaultInjector, FaultPlan
    from repro.runtime.chaos import Fault, FaultKind
    schedule = plan(traffic_dag(), 100, lib, allocator="mba", mapper="sam",
                    vm_sizes="azure-d")
    faults = FaultInjector(FaultPlan(faults=(
        Fault(FaultKind.SLOT_SLOWDOWN, frame=1, task="lookup", frames=2,
              factor=3.0),
        Fault(FaultKind.OPERATOR_ERROR, frame=2, task="parse", count=1),
        Fault(FaultKind.SLOT_STALL, frame=7, task="model", seconds=5.0),
    )), schedule.dag.name)
    ex = StreamExecutor(schedule, lib, clock=VirtualClock(), faults=faults,
                        devices=jax.devices()[:1])
    rep = ex.run(100, n_frames=8, batch=16, seed=5)
    assert (rep.frames, rep.frames_timed_out, rep.retries) == (8, 1, 1)
    assert [(m.task, m.tau, m.tuples, m.busy_seconds)
            for m in ex.measurements()] == VIRTUAL_MEASUREMENTS


#: tuples a frame in the busy test: enough work on the CPU that the spans'
#: own cost, outside the executor's timings, stays far under 1%
BIG = 65536

#: tuples per (task, vm, slot, threads) of the Traffic app at 100 tuples/s
#: after three frames of ``BIG`` tuples, as the executor counted them when
#: it waited for every part
WALL_TUPLES = {
    ("agg", 0, 1, 1): 196608.0, ("archive", 0, 1, 1): 196608.0,
    ("filter", 0, 1, 1): 196608.0, ("lookup", 0, 0, 38): 76236.0,
    ("lookup", 0, 3, 60): 120372.0, ("model", 1, 0, 50): 61440.0,
    ("model", 1, 1, 50): 61440.0, ("model", 1, 2, 50): 61440.0,
    ("model", 1, 3, 10): 12288.0, ("parse", 0, 0, 1): 196608.0,
    ("snk", 0, 1, 1): 196608.0, ("speed", 0, 2, 1): 196608.0,
    ("src", 0, 0, 1): 196608.0,
}


@pytest.fixture
def tracer():
    from repro.obs import Tracer
    prev = obs.set_tracer(Tracer(enabled=True))
    yield obs.get_tracer()
    obs.set_tracer(prev)


def test_wall_clock_busy_is_launch_plus_the_sink_waits(lib, tracer):
    from repro.runtime import WallClock
    schedule = plan(traffic_dag(), 100, lib, allocator="mba", mapper="sam",
                    vm_sizes="azure-d")
    ex = StreamExecutor(schedule, lib, clock=WallClock(),
                        devices=jax.devices()[:1])
    for seq in range(3):
        before = sum(busy for _, busy in ex._measured.values())
        arrays = _frame(BIG, seq, keys=("payload", "value"))
        assert ex.process_frame(MicroBatch(seq, arrays, 0.0), 0.0)[0] == "ok"
        busy = sum(busy for _, busy in ex._measured.values()) - before
        (frame,) = [s for s in tracer.spans if s.name == "executor.frame"
                    and s.attr_dict() == {"seq": seq}]
        spent = sum(s.t1 - s.t0 for s in tracer.spans
                    if s.name in ("executor.launch", "executor.sink_wait")
                    and frame.t0 <= s.t0 and s.t1 <= frame.t1)
        assert busy == pytest.approx(spent, rel=0.01)
    assert {(t, s.vm, s.slot, q): tuples
            for (t, s, q), (tuples, _) in ex._measured.items()} == WALL_TUPLES
    assert all(busy > 0 for _, busy in ex._measured.values())


STATS = json.loads((BENCH / "configs" / "riot-stats.json").read_text())


def _stats_plan(spread):
    """RIoTBench STATS at the configuration's rate, or (``spread``) with
    every kind grouped by a key at 20 tuples/s a thread, so that each is
    routed over three slots at 60 tuples/s."""
    lib = deploy.library(STATS)
    rate = STATS["rate"]
    if spread:
        rate = 60
        for kind, keyed in executor_mod.KEYED.items():
            if keyed.key:
                lib.add(PerfModel.from_points(kind, {1: (20.0, 0.9, 0.05)}))
    schedule = plan(deploy.dataflow(STATS, "stats"), rate, lib,
                    allocator=STATS["allocator"], mapper=STATS["mapper"],
                    vm_sizes=STATS["vm_family"])
    return schedule, lib


@pytest.mark.parametrize("spread", [False, True], ids=["cell", "keyed-3"])
def test_stateful_frames_match_a_wait_after_every_part(monkeypatch, spread):
    schedule, lib = _stats_plan(spread)
    payloads = [sys_frame(7, k, 16 * k, 16, float(STATS["rate"]),
                          STATS["parameters"]) for k in range(6)]

    def run():
        ex = StreamExecutor(schedule, lib, clock=VirtualClock(),
                            devices=jax.devices()[:1])
        keyed = [t for t, g in ex.groups.items() if len(g) > 1
                 and (t, next(iter(g))) in ex._state]
        assert bool(keyed) == spread
        sinks = _sinks(ex, payloads, on_device=False)
        return sinks, {k: {f: np.asarray(v) for f, v in table.items()}
                       for k, table in ex._state.items()}
    launched, tables = run()
    _waited(monkeypatch)
    waited, want = run()
    for got, exp in zip(launched, waited):
        assert sorted(got) == sorted(exp) and got
        for sink in exp:
            _assert_same(got[sink], exp[sink])
    assert sorted(tables, key=str) == sorted(want, key=str) and tables
    for key in want:
        _assert_same(tables[key], want[key])

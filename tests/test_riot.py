"""RIoTBench STATS on the served path: the keyed, stateful kinds against the
plain numpy reference (``bench/reference/riot.py``), fields grouping over
one, three and five slots with state carried across a rebind, the fan-in
union, and a route that compiles nothing new as the mix of keys changes."""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import plan, profiler
from repro.core.perfmodel import PerfModel
from repro.runtime import StreamExecutor, VirtualClock
from repro.runtime import executor as executor_mod
from repro.runtime import operators as ops
from repro.runtime.stream import MicroBatch

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path.append(str(BENCH))

import deploy  # noqa: E402
from loads.keyed_stream import compare, sys_frame  # noqa: E402
from reference import operators as ref_ops  # noqa: E402
from reference import riot  # noqa: E402

CFG = json.loads((BENCH / "configs" / "riot-stats.json").read_text())
P = CFG["parameters"]
LIMITS = json.loads((BENCH / "limits" / "stats.stream.json").read_text()
                    )["limits"]
LIMIT = LIMITS["sink_float_err"]
TASK_OF = {t[1]: t[0] for t in CFG["dags"]["stats"]["tasks"]}
SEED = 2 ** 33 + 41


def test_program_parameters_are_the_configuration_s():
    assert ops.PARAMETERS == P


def test_hash_is_the_reference_s():
    x = np.arange(0, 2 ** 32 - 1, 7919 * 4001, dtype=np.uint32)
    assert np.array_equal(np.asarray(ops.hash32(jnp.asarray(x))),
                          riot.hash32(x))
    assert np.array_equal(ops.hash32(x, xp=np), riot.hash32(x))


def _records(frames, sensors=12, n=16):
    """Parsed frames (host arrays) of a few sensors, so that windows close,
    with about one row in five masked invalid."""
    p = dict(P, sensors=sensors)
    out = []
    for k in range(frames):
        b = riot.senml_parse(sys_frame(SEED, k, k * n, n, 160.0, p),
                             P["fields"], P["sensors"])
        b["valid"] = np.random.default_rng([SEED, k]).random(n) > 0.2
        out.append(b)
    return out


def _inputs(kind, frames):
    """What each kind reads, frame by frame."""
    recs = _records(frames)
    rng = np.random.default_rng(SEED)
    if kind == "sliding_linear_regression":
        return [{**b, "kalman": rng.normal(20, 5, b["obs"].shape)
                 .astype(np.float32)} for b in recs]
    if kind == "accumulate":
        return [{**b, "avg": rng.random(b["obs"].shape, np.float32),
                 "slr": rng.random(b["obs"].shape, np.float32),
                 "distinct": rng.random(16, np.float32) * 1e3,
                 "branch": rng.integers(0, 3, 16).astype(np.int32)}
                for b in recs]
    return recs


def _np(batch):
    return {k: np.asarray(v) for k, v in batch.items()}


@pytest.mark.parametrize("kind", sorted(ops.KEYED))
def test_keyed_kind_matches_the_reference(kind):
    keyed = ops.KEYED[kind]
    fn = jax.jit(keyed.fn)
    state = keyed.init()
    ref = riot.Stats(CFG)
    task = TASK_OF[kind]
    emitted = 0
    for batch in _inputs(kind, 14):
        state, got = fn(state, {k: jnp.asarray(v) for k, v in batch.items()})
        want = ref._keyed(task, batch)
        bad, err = ref_ops.compare({"s": _np(got)}, {"s": want})
        assert bad == 0 and err <= LIMIT
        emitted += int(want["valid"].sum())
    assert emitted > 0


@pytest.mark.parametrize("sensors", [P["sensors"], 2 ** 32 - 1])
def test_senml_parse_matches_the_reference(sensors):
    """Records drawn over the configuration's sensors all parse valid; over
    ids up to 2**32 - 1 those without a state row are masked."""
    payload = sys_frame(SEED, 3, 48, 16, 160.0, dict(P, sensors=sensors))
    got = _np(jax.jit(ops.OPERATORS["senml_parse"])(
        {"payload": jnp.asarray(payload["payload"])}))
    want = riot.senml_parse(payload, P["fields"], P["sensors"])
    assert ref_ops.compare({"s": got}, {"s": want}) == (0, 0.0)
    in_range = want["sensor"].astype(np.uint32) < P["sensors"]
    assert want["valid"].tolist() == in_range.tolist()
    assert in_range.all() == (sensors == P["sensors"])


def test_union_matches_the_reference():
    a = {"sensor": np.arange(4, dtype=np.int32),
         "avg": np.ones((4, 5), np.float32),
         "valid": np.array([1, 0, 1, 1], bool)}
    b = {"sensor": np.arange(6, dtype=np.int32),
         "slr": np.full((6, 5), 2.0, np.float32)}
    c = {"sensor": np.arange(2, dtype=np.int32),
         "distinct": np.full(2, 3.0, np.float32),
         "valid": np.array([0, 1], bool)}
    up = [(0, a), (1, b), (2, c)]
    got = _np(executor_mod._union([{k: jnp.asarray(v) for k, v in x.items()}
                                   for _, x in up], branches=(0, 1, 2)))
    want = riot.union(up)
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].tobytes() == \
            want[k].tobytes(), k
    assert got["branch"].tolist() == [0] * 4 + [1] * 6 + [2] * 2
    assert got["valid"].sum() == 3 + 6 + 1


#: the kinds grouped by a key, which a plan may spread over several slots
SPREAD = tuple(k for k, v in ops.KEYED.items() if v.key) + ("senml_parse",)


def _library(kinds=SPREAD):
    """The configuration's profiles, with ``kinds`` at 20 tuples/s on a
    thread that fills a slot, so that their thread and slot counts grow
    with the rate."""
    lib = deploy.library(CFG)
    for kind in kinds:
        lib.add(PerfModel.from_points(kind, {1: (20.0, 0.9, 0.05)}))
    return lib


def _plan(rate, kinds=SPREAD):
    return plan(deploy.dataflow(CFG, "stats"), rate, _library(kinds),
                allocator="mba", mapper="sam", vm_sizes="azure-d")


def _run(executor, frames, first=0):
    sinks = []
    for k in range(first, first + frames):
        payload = sys_frame(SEED, k, 16 * k, 16, 160.0, P)
        status, _ = executor.process_frame(MicroBatch(k, payload, 0.0), 0.0)
        assert status == "ok"
        sinks.append({n: _np(o) for n, o in
                      executor.last_sink_outputs.items()})
    return sinks


def _keyed_slots(schedule):
    groups = deploy.mapping_groups(schedule.mapping)
    return {t: len(groups[TASK_OF[k]]) for k, t in
            (("average", "avg"), ("kalman_filter", "kalman"),
             ("sliding_linear_regression", "slr"),
             ("distinct_approx_count", "dac"))}


def _without_service(sinks):
    # the upload's lookup sums each part, so it follows the upload's cut
    return [{n: {k: v for k, v in o.items() if k != "service"}
             for n, o in s.items()} for s in sinks]


def test_one_three_and_rebound_slots_give_the_same_outputs():
    one, three, five = _plan(10), _plan(60), _plan(100)
    for schedule, slots in ((one, 1), (three, 3), (five, 5)):
        # the globally grouped distinct count keeps to one slot
        assert _keyed_slots(schedule) == {"avg": slots, "kalman": slots,
                                          "slr": slots, "dac": 1}
    dev = jax.devices()[:1]
    lib = _library()
    a = _run(StreamExecutor(one, lib, clock=VirtualClock(), devices=dev), 12)
    b = _run(StreamExecutor(three, lib, clock=VirtualClock(), devices=dev),
             12)
    ex = StreamExecutor(three, lib, clock=VirtualClock(), devices=dev)
    c = _run(ex, 6)
    info = ex.rebind(five)
    assert info.state_moved_bytes > 0
    c += _run(ex, 6, first=6)
    for got in (b, c):
        for x, y in zip(_without_service(a), _without_service(got)):
            assert ref_ops.compare(x, y) == (0, 0.0)
    # and the reference, which takes no schedule for the keyed kinds
    ref = riot.Stats(CFG)
    groups = deploy.mapping_groups(five.mapping)
    for k, got in enumerate(c):
        want = ref.frame(sys_frame(SEED, k, 16 * k, 16, 160.0, P),
                         groups if k >= 6 else
                         deploy.mapping_groups(three.mapping))
        bad, err, service, _ = compare(got, want)
        assert bad == 0 and err <= LIMIT and service <= LIMITS["service_err"]


def test_globally_grouped_task_over_two_slots_is_refused():
    spread = _plan(60, kinds=(*SPREAD, "distinct_approx_count"))
    assert _keyed_slots(spread)["dac"] == 3
    dev = jax.devices()[:1]
    with pytest.raises(ValueError, match="globally grouped"):
        StreamExecutor(spread, _library(), clock=VirtualClock(), devices=dev)
    ex = StreamExecutor(_plan(60), _library(), clock=VirtualClock(),
                        devices=dev)
    groups, state = ex.groups, dict(ex._state)
    with pytest.raises(ValueError, match="globally grouped"):
        ex.rebind(spread)
    assert ex.groups is groups and ex._state == state
    assert _run(ex, 1)


def test_rebind_moves_each_key_to_its_new_owner():
    three, five = _plan(60), _plan(100)
    lib = _library()
    ex = StreamExecutor(three, lib, clock=VirtualClock(),
                        devices=jax.devices()[:1])
    _run(ex, 4)
    before = {k: {n: np.asarray(v) for n, v in t.items()}
              for k, t in ex._state.items()}
    old_groups = ex.groups
    ex.rebind(five)
    keyed = ops.KEYED["kalman_filter"]
    old = executor_mod._row_owners(old_groups["kalman"], keyed)
    new = executor_mod._row_owners(ex.groups["kalman"], keyed)
    old_slots = executor_mod._slot_order(old_groups["kalman"])
    new_slots = executor_mod._slot_order(ex.groups["kalman"])
    assert (old != new).any()
    for row in range(keyed.rows):
        got = np.asarray(ex._state[("kalman", new_slots[new[row]])]["x"][row])
        want = before[("kalman", old_slots[old[row]])]["x"][row]
        assert got.tobytes() == want.tobytes()


def test_changing_key_mixes_compile_nothing_after_warm_up():
    ex = StreamExecutor(_plan(60), _library(), clock=VirtualClock(),
                        devices=jax.devices()[:1])
    _run(ex, 2)
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for k, sensors in enumerate((1, 2, 1000, 3)):
            payload = sys_frame(SEED, 50 + k, 16 * (50 + k), 16, 160.0,
                                dict(P, sensors=sensors))
            status, _ = ex.process_frame(MicroBatch(50 + k, payload, 0.0),
                                         0.0)
            assert status == "ok"
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


@pytest.fixture
def metrics():
    obs.REGISTRY.reset()
    obs.REGISTRY.enable()
    yield
    obs.REGISTRY.disable()
    obs.REGISTRY.reset()


def test_keyed_route_counts_its_time_and_state(metrics):
    three, five = _plan(60), _plan(100)
    ex = StreamExecutor(three, _library(), clock=VirtualClock(),
                        devices=jax.devices()[:1])
    _run(ex, 2)
    snap = obs.snapshot()
    assert snap["repro_executor_keyroute_seconds_total"]["value"] > 0
    held = snap['repro_executor_state_bytes{task="kalman"}']["value"]
    assert held == 3 * 2 * ops.SYS_SENSORS * ops.SYS_FIELDS * 4
    moved = ex.rebind(five).state_moved_bytes
    assert obs.snapshot()["repro_executor_state_moved_bytes_total"][
        "value"] == moved > 0


@pytest.mark.parametrize("kind", sorted(profiler.STATS_BODIES))
def test_single_tuple_body_runs_for_alg1(kind):
    """Each STATS kind's single-tuple body, which Alg. 1 profiles, takes a
    tuple a call with the program's parameters."""
    body = profiler.STATS_BODIES[kind](ops)
    outs = [body() for _ in range(4 * ops.W_AVG * ops.SYS_SENSORS // 100)]
    assert body.i == len(outs)
    if kind == "distinct_approx_count":
        x = np.arange(0, 2 ** 32 - 1, 7919 * 4001, dtype=np.uint32)
        assert [profiler.fmix32(int(v)) for v in x] == \
            riot.hash32(x).tolist()
        assert 0 < outs[-1] < 4 * ops.SYS_SENSORS


def test_service_is_compared_on_its_circle():
    want = {"s": {"service": np.array([0.004, 500.0], np.float32),
                  "avg": np.array([1.0], np.float32)}}
    got = {"s": {"service": np.array([999.996, 500.25], np.float32),
                 "avg": np.array([1.0], np.float32)}}
    bad, err, service, by_field = compare(got, want)
    assert (bad, err) == (0, 0.0)
    assert service == pytest.approx(0.25) == by_field["service"]
    bad, _, _, _ = compare({"s": {"avg": got["s"]["avg"]}}, want)
    assert bad == 1


def test_load_refuses_parameters_the_program_does_not_hold():
    from loads.keyed_stream import LOAD
    cfg = dict(CFG, parameters=dict(P, sensors=P["sensors"] + 1))
    load = LOAD(cfg, {"frame_tuples": 16}, SEED, jax.devices()[:1], LIMITS)
    with pytest.raises(ValueError, match="parameters"):
        load.setup(1.0)

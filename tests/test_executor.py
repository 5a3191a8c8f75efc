"""The executor's route: a frame cut over a task's slot groups, and the
parts' outputs interleaved back, each in one compiled program, against the
eager per-key cut and concatenation they replace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import linear_dag, plan, traffic_dag
from repro.runtime import StreamExecutor, VirtualClock
from repro.runtime import executor as executor_mod
from repro.runtime.stream import MicroBatch

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
@pytest.mark.parametrize("dag, rate", [(traffic_dag, 100), (linear_dag, 120)],
                         ids=["traffic-100", "linear-120"])
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

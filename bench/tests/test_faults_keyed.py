"""A run of ``stats.stream`` or ``traffic.stream4`` with the timed path
broken underneath reads ``correct`` false.

Each test drives a whole run of a cell on the CPU (the harness's look for
a TPU skipped) with one fault planted in the program: keyed state reset
every frame, a key's tuples sent to two owners, the fan-in union
dropping a branch, and on four virtual devices the gather between chips
left out."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np

import deploy
import run as bench_run

SEED = 2 ** 31 + 77
BENCH = pathlib.Path(__file__).resolve().parents[1]


def _correct(workload: str) -> bool:
    result = bench_run.run_cell(workload, SEED, 1.5, False,
                                require_tpu=False)
    assert result["attempted"] > 0
    return result["correct"]


def _spread_keyed_tasks(monkeypatch):
    """Profiles that give the parse and every kind grouped by a key one
    thread a slot at 20 tuples/s, so that the plan spreads each such task
    over several slots."""
    from repro.core.perfmodel import PerfModel
    from repro.runtime.operators import KEYED
    library = deploy.library

    def spread(cfg):
        lib = library(cfg)
        for kind in (*(k for k, v in KEYED.items() if v.key),
                     "senml_parse"):
            lib.add(PerfModel.from_points(kind, {1: (20.0, 0.9, 0.05)}))
        return lib
    monkeypatch.setattr(deploy, "library", spread)


def test_state_reset_every_frame(monkeypatch):
    from repro.runtime.executor import StreamExecutor
    process = StreamExecutor.process_frame

    def reset(self, frame, interval):
        self._carry_state({}, {}, {})
        return process(self, frame, interval)
    monkeypatch.setattr(StreamExecutor, "process_frame", reset)
    assert not _correct("stats.stream")


def test_spread_keyed_tasks_run_correct(monkeypatch):
    _spread_keyed_tasks(monkeypatch)
    assert _correct("stats.stream")


def test_keys_sent_to_two_owners(monkeypatch):
    import jax.numpy as jnp
    from repro.runtime import executor
    _spread_keyed_tasks(monkeypatch)
    keyroute = executor._keyroute

    def split_keys(arrays, key, threads):
        # a key's tuples at odd positions of a frame go to the next slot
        owner, _ = keyroute(arrays, key=key, threads=threads)
        k = arrays[key]
        odd = jnp.arange(k.shape[0]) % 2 == 1
        owner = jnp.where(odd, (owner + 1) % len(threads), owner)
        valid = arrays.get("valid", jnp.ones(k.shape, bool))
        return owner, [{**arrays, "valid": valid & (owner == s)}
                       for s in range(len(threads))]
    monkeypatch.setattr(executor, "_keyroute", split_keys)
    assert not _correct("stats.stream")


def test_union_drops_a_branch(monkeypatch):
    import jax.numpy as jnp
    from repro.runtime import executor
    union = executor._union

    def drop_last(ins, branches):
        # the last in-edge's tuples never reach the union
        last = ins[-1]
        n = next(iter(last.values())).shape[0]
        gone = {**last, "valid": jnp.zeros((n,), bool)}
        return union([*ins[:-1], gone], branches=branches)
    monkeypatch.setattr(executor, "_union", drop_last)
    assert not _correct("stats.stream")


FOUR_CHIPS = """
import json, sys, types
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import run as bench_run
from repro.runtime import executor
if {fault}:
    def local_only(x, device=None, *a, **k):
        # a gather of parts leaves out those on another chip
        if isinstance(x, list) and x and isinstance(x[0], dict):
            x = [o for o in x
                 if next(iter(next(iter(o.values())).devices())) == device]
        return jax.device_put(x, device, *a, **k)
    names = {{k: getattr(jax, k) for k in dir(jax) if not k.startswith('__')}}
    executor.jax = types.SimpleNamespace(**dict(names, device_put=local_only))
r = bench_run.run_cell("traffic.stream4", {seed}, 1.5, False,
                       require_tpu=False)
print(json.dumps({{"correct": r["correct"], "chips": r["device"]["count"],
                  "checks": r["checks"]}}))
"""


def _four_chips(fault: bool) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                             fault=fault, seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_traffic_on_four_chips_runs_correct():
    r = _four_chips(False)
    assert r["chips"] == 4 and r["correct"]


def test_gather_between_chips_left_out():
    r = _four_chips(True)
    assert r["chips"] == 4 and not r["correct"]
    assert r["checks"]["sink_mismatch"]["value"] > 0 or \
        not np.isfinite(r["checks"]["sink_float_err"]["value"]) or \
        r["checks"]["sink_float_err"]["value"] > \
        r["checks"]["sink_float_err"]["limit"]

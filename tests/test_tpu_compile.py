"""Ahead-of-time compiles of the device path for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, compiles the co-simulation scan kernel, one vmapped search
bucket and the stream operators at the sizes ``chip_smoke.py`` drives (the
stateful STATS kinds with their whole state tables), and
raises what the chip's compiler would raise (unsupported ops, programs that
do not fit).  The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the worker
running this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import DataflowSimulator, paper_library, plan, traffic_dag
from repro.core.predictor import build_group_index
from repro.core.search import (bucket_row_slices, generate_candidates,
                               shape_buckets)
from repro.core.simulator import SweepBatch, _sweep_steps, get_scan_kernel
from repro.jaxenv import x64
from repro.runtime.operators import KEYED, OPERATORS

#: the executor's frame: LiveFleet's 16-tuple batch of 256-byte payloads
FRAME, PAYLOAD = 16, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def lib():
    return paper_library()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_tpu(compiled) -> bool:
    return all(d.platform == "tpu"
               for s in jax.tree.leaves(compiled.output_shardings)
               for d in s.device_set)


def test_scan_kernel_compiles_for_v5e(lib, one_chip):
    """The traffic DAG's co-simulation kernel at the sweep defaults: 60 s
    at dt 0.05 (1,200 ticks) over a 64-point rate grid, in float64."""
    sched = plan(traffic_dag(), 100, lib, mapper="sam")
    batch = SweepBatch([DataflowSimulator(sched.dag, sched.allocation,
                                          sched.mapping, lib)])
    spec = batch.spec
    steps, sample_every, s0 = _sweep_steps(60.0, 0.05, 5.0, 0.25)
    assert steps == 1200
    K = 64
    fn = get_scan_kernel(spec.row_slices, spec.in_edges, spec.sink_groups,
                         len(spec.slots))
    with x64():
        f64 = jnp.float64
        compiled = fn.lower(
            _sds((spec.n_groups, K), f64, one_chip),
            _sds((spec.n_rows, K), f64, one_chip),
            _sds((), f64, one_chip),
            _sds((spec.n_groups,), f64, one_chip),
            _sds((spec.n_groups,), jnp.int32, one_chip),
            _sds((len(batch._hops_flat),), f64, one_chip),
            steps=steps, sample_every=sample_every, s0=s0).compile()
    assert _on_tpu(compiled)
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_search_bucket_compiles_for_v5e(lib, one_chip):
    """The largest shape bucket of the candidate pool that
    ``plan(traffic_dag(), 100, mapper="search")`` searched, through the
    vmapped kernel at the search defaults (10 s at dt 0.1, an 11-point
    rate grid)."""
    dag = traffic_dag()
    sched = plan(dag, 100, lib, mapper="search")
    cands = generate_candidates(dag, sched.allocation, sched.vms, lib)
    gis = [build_group_index(dag, sched.allocation, c.mapping, lib)
           for c in cands]
    (pad_counts, s_pad), idxs = max(shape_buckets(gis).items(),
                                    key=lambda kv: len(kv[1]))
    row_slices = bucket_row_slices(pad_counts)
    C, G = len(idxs), row_slices[-1][1]
    assert C > 1
    gi0 = gis[0]
    sink_rows = [gi0.task_of[t.name] for t in dag.sinks()]
    n_hops = sum(len(e) for e in gi0.in_edges)
    steps, sample_every, s0 = _sweep_steps(10.0, 0.1, 2.5, 0.25)
    K = 11
    fn = get_scan_kernel(row_slices, gi0.in_edges, [sink_rows], s_pad,
                         batched=True)
    with x64():
        f64 = jnp.float64
        compiled = fn.lower(
            _sds((C, G, K), f64, one_chip),
            _sds((len(gi0.tasks), K), f64, one_chip),
            _sds((), f64, one_chip),
            _sds((C, G), f64, one_chip),
            _sds((C, G), jnp.int32, one_chip),
            _sds((C, n_hops), f64, one_chip),
            steps=steps, sample_every=sample_every, s0=s0).compile()
    assert _on_tpu(compiled)
    assert compiled.memory_analysis().output_size_in_bytes > 0


@pytest.mark.parametrize("kind", sorted(OPERATORS))
def test_operator_compiles_for_v5e(kind, one_chip):
    """Every operator body at the executor's frame shape."""
    frame = {"payload": _sds((FRAME, PAYLOAD), np.uint8, one_chip),
             "value": _sds((FRAME,), np.float32, one_chip)}
    compiled = jax.jit(OPERATORS[kind]).lower(frame).compile()
    assert _on_tpu(compiled)


def _stats_frame(kind, one_chip):
    """What a STATS kind reads at the executor's frame: 16 parsed SYS
    records, or the accumulator's union of its three in-edges (48 rows)."""
    from repro.runtime.operators import SYS_FIELDS
    n = 3 * FRAME if kind == "accumulate" else FRAME
    frame = {"sensor": _sds((n,), np.int32, one_chip),
             "ts": _sds((n,), np.int32, one_chip),
             "obs": _sds((n, SYS_FIELDS), np.float32, one_chip),
             "valid": _sds((n,), np.bool_, one_chip)}
    if kind == "sliding_linear_regression":
        frame["kalman"] = _sds((n, SYS_FIELDS), np.float32, one_chip)
    if kind == "accumulate":
        frame.update(avg=_sds((n, SYS_FIELDS), np.float32, one_chip),
                     slr=_sds((n, SYS_FIELDS), np.float32, one_chip),
                     distinct=_sds((n,), np.float32, one_chip),
                     branch=_sds((n,), np.int32, one_chip))
    return frame


@pytest.mark.parametrize("kind", sorted(KEYED))
def test_stateful_operator_compiles_for_v5e(kind, one_chip):
    """Every stateful kind with its whole state table (1,000 sensors) at
    the executor's frame shape."""
    state = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                         jax.eval_shape(KEYED[kind].init))
    compiled = jax.jit(KEYED[kind].fn).lower(
        state, _stats_frame(kind, one_chip)).compile()
    assert _on_tpu(compiled)


def test_keyed_route_compiles_for_v5e(one_chip):
    """The keyed route and merge over three slots."""
    from repro.runtime import executor
    frame = _stats_frame("average", one_chip)
    route = executor._keyroute.lower(frame, key="sensor",
                                     threads=(2, 1, 3)).compile()
    assert _on_tpu(route)
    owner = _sds((FRAME,), np.int32, one_chip)
    merge = executor._keymerge.lower([frame] * 3, owner,
                                     parts=(0, 1, 2)).compile()
    assert _on_tpu(merge)

"""Discrete-time (fluid) simulation of a scheduled dataflow.

Stands in for the paper's live Apache Storm runs: tuple streams flow through
the mapped DAG, each (task, slot) group services at the model capacity
``I_t(q)`` (degraded by the §8.4.2 CPU-oversubscription penalty), routing
follows shuffle or slot-aware policy, queues accumulate when a group is
overloaded, and the stability test is the paper's latency-slope criterion
(the slope is measured in seconds of latency per second of run time, so the
verdict does not depend on ``latency_sample_every``).

The simulator is what the benchmark harness calls the *actual* behaviour.  It
deliberately contains effects the schedule planner does NOT model (routing
skew, oversubscription throttling, network hops), which is what produces the
planned-vs-actual gaps reported in Figs. 7–13.  Hop latency between two
tasks is the *flow-weighted* expectation over their (src group, dst group)
pairs — each pair weighted by the source group's routed fraction times the
destination group's routing fraction — so shuffle and slot-aware routing see
different expected hops for the same mapping.

Engines
-------
Internally the engine is fully vectorized: per-group queues and capacities
live in flat arrays keyed by a precomputed :class:`GroupIndex`, with the
*rate sweep* as a trailing array axis.  Two interchangeable engines advance
the ``(G, K)`` state:

``engine="numpy"``   the reference implementation — a Python tick loop over
                     numpy arrays (the default; no compile cost).
``engine="scan"``    a jitted :func:`jax.lax.scan` kernel: the per-row
                     gather/scatter indices (in-edge sources and
                     multiplicities, contiguous group slices, slot ids) are
                     precomputed from the :class:`GroupIndex` into a
                     :class:`_SweepSpec`, the tick body is pure array ops,
                     and the whole time loop runs inside one XLA program
                     (float64, matching numpy to ~1e-12).  After the one-off
                     compile, large sweeps (50+ rates x long horizons) run
                     an order of magnitude faster.

``simulate_sweep(omegas)`` runs a whole vector of input rates through one
time loop; ``run(omega)`` is the single-column special case, and
``max_stable_rate`` refines the stability boundary with multi-point sweep
passes instead of one-rate-at-a-time bisection.  :class:`SweepBatch`
co-simulates *several* independently scheduled dataflows (e.g. every DAG of
a :class:`~repro.core.fleet.FleetPlan`) in ONE time loop over the union of
their slot pools — busy time lands on shared slots additively, which is what
``repro.core.fleet.simulate_fleet`` uses for fleet predicted-vs-actual
studies.

Compiled scan kernels are cached at module level keyed by the spec's
*structural* signature (:func:`get_scan_kernel`): placement data (routing
fractions, slot ids, hop latencies) is traced, not baked, so every batch,
``max_stable_rate`` bisection pass, fleet replan, and mapper-search run with
the same structure reuses one kernel — including the ``jax.vmap``-over-
candidate-mappings variant the simulation-guided search
(:mod:`repro.core.search`) evaluates whole candidate pools with.
"""

from __future__ import annotations

import dataclasses
import random
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .allocation import Allocation
from .dag import Dataflow
from .mapping import Mapping as ThreadMapping, SlotId
from .perfmodel import ModelLibrary
from .predictor import (GroupIndex, build_group_index, effective_capacities,
                        effective_capacity_matrix, slot_groups)
from .routing import RoutingPolicy, group_rates
from ..obs import metrics as _obs_metrics
from ..obs.trace import span as _obs_span

#: Network hop latencies (s): same slot / same VM / cross VM.
HOP_SAME_SLOT = 0.0002
HOP_SAME_VM = 0.001
HOP_CROSS_VM = 0.005

#: §5.1 stability criterion: a run is stable when the fitted latency slope
#: does not exceed this, in seconds of latency per second of run time.
STABLE_SLOPE_PER_S = 1e-3

ENGINES = ("numpy", "scan")

#: Module-level cache of compiled ``lax.scan`` kernels, keyed by the
#: *structural* signature of a :class:`_SweepSpec` (row slices, in-edge
#: wiring, sink rows, slot count — everything shape-like).  Placement data
#: (routing fractions, slot ids, hop latencies) is passed to the kernel as
#: traced arrays, so two specs that differ only in where threads sit share
#: ONE compiled kernel.  Repeated searches, ``max_stable_rate`` bisection
#: passes, and fleet replans therefore stop re-tracing; ``jax.jit``'s own
#: executable cache (per shape / static args) lives on the cached callable.
_KERNEL_CACHE: Dict[tuple, object] = {}
_KERNEL_STATS = {"hits": 0, "misses": 0}
#: Guards both dicts above: searches and fleet replans may request kernels
#: from worker threads, and an unlocked check-then-insert would double-trace
#: the same structure and tear the hit/miss counters.
_KERNEL_LOCK = threading.Lock()


def scan_kernel_cache_stats() -> Dict[str, int]:
    """Hit/miss counters plus compiled-executable counts for the module-level
    scan-kernel cache (``compiled`` sums each cached callable's jit cache, so
    a delta of zero between two runs proves zero recompilation)."""
    with _KERNEL_LOCK:
        entries = list(_KERNEL_CACHE.values())
        stats = dict(_KERNEL_STATS)
    compiled = 0
    for fn in entries:
        size = getattr(fn, "_cache_size", None)
        compiled += int(size()) if callable(size) else 0
    return {"entries": len(entries), "hits": stats["hits"],
            "misses": stats["misses"], "compiled": compiled}


def scan_kernel_cache_clear() -> None:
    with _KERNEL_LOCK:
        _KERNEL_CACHE.clear()
        _KERNEL_STATS["hits"] = _KERNEL_STATS["misses"] = 0


def _kernel_cache_collector(registry: "_obs_metrics.MetricsRegistry") -> None:
    """Pull-style obs bridge: publish cache stats at snapshot time."""
    stats = scan_kernel_cache_stats()
    registry.gauge("repro_scan_kernel_cache_entries",
                   "Distinct compiled scan-kernel structures cached."
                   ).set(stats["entries"])
    registry.gauge("repro_scan_kernel_cache_hits_total",
                   "Scan-kernel cache lookups served from cache."
                   ).set(stats["hits"])
    registry.gauge("repro_scan_kernel_cache_misses_total",
                   "Scan-kernel cache lookups that compiled."
                   ).set(stats["misses"])
    lookups = stats["hits"] + stats["misses"]
    registry.gauge("repro_scan_kernel_cache_hit_ratio",
                   "hits / (hits + misses) of the scan-kernel cache."
                   ).set(stats["hits"] / lookups if lookups else 0.0)


_obs_metrics.register_collector(_kernel_cache_collector)


def _kernel_key(row_slices, in_edges, sink_groups, n_slots: int,
                batched: bool) -> tuple:
    return (bool(batched), int(n_slots),
            tuple((int(lo), int(hi)) for lo, hi in row_slices),
            tuple(tuple((int(s), float(m)) for s, m in e) for e in in_edges),
            tuple(tuple(int(r) for r in rows) for rows in sink_groups))


def get_scan_kernel(row_slices, in_edges, sink_groups, n_slots: int,
                    *, batched: bool = False):
    """The compiled sweep kernel for one spec structure, from the module
    cache.  ``batched=True`` returns the ``jax.vmap``-over-candidates variant
    (leading candidate axis on caps / fractions / slot ids / hops)."""
    key = _kernel_key(row_slices, in_edges, sink_groups, n_slots, batched)
    with _KERNEL_LOCK:
        fn = _KERNEL_CACHE.get(key)
        if fn is None:
            _KERNEL_STATS["misses"] += 1
            with _obs_span("scan_kernel_compile", slots=int(n_slots),
                           batched=bool(batched)):
                fn = _make_scan_kernel(row_slices, in_edges, sink_groups,
                                       n_slots, batched=batched)
            _KERNEL_CACHE[key] = fn
        else:
            _KERNEL_STATS["hits"] += 1
    return fn


def _sweep_steps(duration: float, dt: float, warmup: float,
                 latency_sample_every: float) -> Tuple[int, int, int]:
    """(steps, sample_every, s0) — the shared discretization of a sweep.

    The measurement window starts at the first tick at or past ``warmup``;
    runs too short to have one fall back to the whole run (mirroring the
    latency tail-window fallback in ``results_from_raw``)."""
    steps = int(duration / dt)
    sample_every = max(1, int(latency_sample_every / dt))
    s0 = int(np.ceil(warmup / dt - 1e-9))
    if s0 >= steps or s0 < 0:
        s0 = 0
    return steps, sample_every, s0


@dataclasses.dataclass
class SimResult:
    omega: float
    stable: bool
    latency_slope: float           # seconds of latency per second of run time
    mean_latency: float            # end-to-end seconds (stable portion)
    p99_latency: float
    latency_samples: List[float]
    queue_total: float             # final total queued tuples
    #: per slot, the time-averaged SUM of its groups' thread utilizations —
    #: a slot hosting several saturated groups reads above 1.0
    slot_busy: Dict[SlotId, float]


@dataclasses.dataclass
class SweepRaw:
    """Raw engine output for one sweep (shared by both engines).

    ``latency`` holds the path latency at every sample tick per *output
    group* (one per co-simulated dataflow, in :class:`SweepBatch` order);
    ``busy``/``served`` are accumulated only over the measured window
    (post-warmup ticks) of ``window`` seconds.
    """

    queues: np.ndarray        # (G, K) final queue length per group
    busy: np.ndarray          # (S, K) busy-seconds within the window
    served: np.ndarray        # (G, K) tuples served within the window
    realized: np.ndarray      # (T, K) final-tick realized output rates
    latency: np.ndarray       # (n_samples, n_out, K)
    sample_times: np.ndarray  # (n_samples,)
    steps: int                # ticks simulated (realized horizon steps * dt)
    s0: int                   # first tick counted into busy/served
    dt: float                 # tick length (s)
    window: float             # (steps - s0) * dt seconds


@dataclasses.dataclass
class _SweepSpec:
    """Precomputed gather/scatter index arrays for the tick kernels.

    Flattens one or more :class:`GroupIndex` instances (tasks stacked in topo
    order, groups contiguous per task, slots deduplicated across dataflows)
    so both engines' step bodies are pure array ops over the ``(G, K)``
    state.
    """

    row_slices: List[Tuple[int, int]]          # (T,) group span per task row
    in_edges: List[List[Tuple[int, float]]]    # (T,) (src row, multiplier)
    hops: List[List[float]]                    # (T,) hop latency per in-edge
    g_frac: np.ndarray                         # (G,) routing fraction
    g_slot: np.ndarray                         # (G,) union slot row
    g_task: np.ndarray                         # (G,) owning task row
    slots: List[SlotId]                        # (S,) union slot pool
    sink_groups: List[List[int]]               # per output: sink task rows

    @property
    def n_rows(self) -> int:
        return len(self.row_slices)

    @property
    def n_groups(self) -> int:
        return len(self.g_frac)


def _hop_latency(gi, src_row: int, dst_row: int) -> float:
    """Expected network hop latency between two tasks' thread groups,
    weighted by the tuple flow each (src group, dst group) pair actually
    carries: the source group's routed fraction times the destination
    group's routing fraction (both rate-independent under either policy).

    An unweighted average would count a 9-thread destination group the
    same as a 2-thread one; with flow weights, shuffle and slot-aware
    routing see different expected hop latencies for the same mapping.
    """
    sl_s, sl_d = gi.task_slice(src_row), gi.task_slice(dst_row)
    if sl_s.start == sl_s.stop or sl_d.start == sl_d.stop:
        return 0.0
    w = gi.g_frac[sl_s, None] * gi.g_frac[None, sl_d]
    vm_s = np.array([gi.slots[s].vm for s in gi.g_slot[sl_s]])
    vm_d = np.array([gi.slots[s].vm for s in gi.g_slot[sl_d]])
    hop = np.where(gi.g_slot[sl_s, None] == gi.g_slot[None, sl_d],
                   HOP_SAME_SLOT,
                   np.where(vm_s[:, None] == vm_d[None, :],
                            HOP_SAME_VM, HOP_CROSS_VM))
    total_w = w.sum()
    if total_w <= 0:        # degenerate zero-fraction groups: fall back
        return float(hop.mean())
    return float((w * hop).sum() / total_w)


def edge_hop_latencies(gi) -> List[List[float]]:
    """Per task row, hop latency of each in-edge (rate-independent) for a
    prebuilt :class:`~repro.core.predictor.GroupIndex` — shared by the
    simulator and the mapper-search candidate evaluator."""
    return [[_hop_latency(gi, src, row) for src, _ in gi.in_edges[row]]
            for row in range(len(gi.tasks))]


class DataflowSimulator:
    """Fluid-flow simulation with per-group queues at dt resolution."""

    def __init__(self, dag: Dataflow, alloc: Allocation,
                 mapping: ThreadMapping, models: ModelLibrary,
                 *, policy: RoutingPolicy = RoutingPolicy.SHUFFLE,
                 cpu_penalty: bool = True, seed: int = 0,
                 engine: str = "numpy", gi: Optional[GroupIndex] = None):
        if engine not in ENGINES:
            raise ValueError(f"unknown simulator engine {engine!r}")
        self.dag = dag
        self.alloc = alloc
        self.mapping = mapping
        self.models = models
        self.policy = policy
        self.cpu_penalty = cpu_penalty
        self.engine = engine
        self.groups = slot_groups(mapping, alloc)
        self.rng = random.Random(seed)
        # ``gi`` reuses a prebuilt index for exactly (dag, alloc, mapping,
        # policy) — e.g. the one a FleetEntry already carries — so repeated
        # co-simulations of a live fleet (the online controller's
        # between-events loop) skip the flattening pass entirely
        self.gi = gi if gi is not None \
            else build_group_index(dag, alloc, mapping, models, policy)
        self._hops = edge_hop_latencies(self.gi)
        self._sink_rows = [self.gi.task_of[t.name] for t in dag.sinks()]
        self._batch: Optional[SweepBatch] = None

    # -- main entry ------------------------------------------------------------
    def run(self, omega: float, *, duration: float = 60.0, dt: float = 0.05,
            warmup: float = 5.0, latency_sample_every: float = 0.25,
            engine: Optional[str] = None) -> SimResult:
        return self.simulate_sweep(
            [omega], duration=duration, dt=dt, warmup=warmup,
            latency_sample_every=latency_sample_every, engine=engine)[0]

    def simulate_sweep(self, omegas: Sequence[float], *,
                       duration: float = 60.0, dt: float = 0.05,
                       warmup: float = 5.0,
                       latency_sample_every: float = 0.25,
                       engine: Optional[str] = None) -> List[SimResult]:
        """Simulate every input rate in ``omegas`` through ONE time loop.

        All per-group state is a ``(G, K)`` array (groups x rates); each tick
        advances the whole sweep at once.  Results match per-rate ``run``
        calls (``run`` *is* the K=1 column of this loop).  ``engine``
        overrides the instance default (``"numpy"`` or ``"scan"``).
        """
        if self._batch is None:
            self._batch = SweepBatch([self])
        return self._batch.simulate(
            [omegas], duration=duration, dt=dt, warmup=warmup,
            latency_sample_every=latency_sample_every,
            engine=engine or self.engine)[0]

    def sweep_raw(self, omegas: Sequence[float], *,
                  duration: float = 60.0, dt: float = 0.05,
                  warmup: float = 5.0, latency_sample_every: float = 0.25,
                  engine: Optional[str] = None) -> SweepRaw:
        """The raw engine state for a sweep (queues, busy, served, realized,
        latency series) — the engine-equivalence contract surface."""
        if self._batch is None:
            self._batch = SweepBatch([self])
        return self._batch.sweep_raw(
            [omegas], duration=duration, dt=dt, warmup=warmup,
            latency_sample_every=latency_sample_every,
            engine=engine or self.engine)

    # -- derived measurements ---------------------------------------------------
    def max_stable_rate(self, *, lo: float = 1.0, hi: float = 1e5,
                        tol: float = 0.01, duration: float = 30.0,
                        dt: float = 0.05, probes: int = 8,
                        engine: Optional[str] = None) -> float:
        """Highest stable DAG rate (the paper's empirical 'actual rate':
        increase until the latency slope turns positive).

        Each refinement pass sweeps ``probes`` interior rates through one
        vectorized ``simulate_sweep`` call, shrinking the bracket by
        ``probes + 1`` per pass — the sweep-engine replacement for
        one-rate-at-a-time bisection.  Every pass reuses the same sweep
        shape, so the ``"scan"`` engine compiles once for all passes.
        """
        # quick analytic bracket from capacities
        from .predictor import predict_max_rate
        analytic = predict_max_rate(self.dag, self.alloc, self.mapping,
                                    self.models, self.policy)
        hi = min(hi, analytic * 1.5 + 10)
        lo_ok, hi_bad = 0.0, hi
        while hi_bad - lo_ok > tol * max(1.0, lo_ok):
            mids = np.linspace(lo_ok, hi_bad, probes + 2)[1:-1]
            stable = [r.stable for r in self.simulate_sweep(
                mids, duration=duration, dt=dt, engine=engine)]
            n_ok = next((i for i, s in enumerate(stable) if not s),
                        len(stable))
            if n_ok > 0:
                lo_ok = float(mids[n_ok - 1])
            if n_ok < len(mids):
                hi_bad = float(mids[n_ok])
            # every probe stable: lo_ok moved to mids[-1], so the bracket
            # still shrank by (probes+1) and the loop converges toward hi
        return lo_ok


# ---------------------------------------------------------------------------
# Co-simulation of one or more dataflows through one time loop.
# ---------------------------------------------------------------------------

class SweepBatch:
    """Co-simulate several scheduled dataflows' rate sweeps in ONE time loop.

    The simulators' :class:`GroupIndex` structures are flattened into one
    :class:`_SweepSpec` (task rows stacked, groups contiguous, slot pools
    deduplicated by :class:`SlotId`), so a fleet of independent DAGs advances
    as a single ``(G_total, K)`` array pass per tick — and, under
    ``engine="scan"``, as a single jitted ``lax.scan`` over ticks.  Slots
    shared between dataflows accumulate busy time from all of them (the
    shared-VM-pool semantics ``repro.core.fleet.simulate_fleet`` relies on);
    each per-DAG :class:`SimResult` reports the slots its own mapping uses.
    """

    def __init__(self, sims: Sequence[DataflowSimulator]):
        if not sims:
            raise ValueError("SweepBatch needs at least one simulator")
        self.sims = list(sims)
        self._build_spec()
        parts = [np.asarray(h, dtype=float) for h in self.spec.hops]
        self._hops_flat = (np.concatenate(parts) if parts
                           else np.zeros(0, dtype=float))

    def _build_spec(self) -> None:
        row_slices: List[Tuple[int, int]] = []
        in_edges: List[List[Tuple[int, float]]] = []
        hops: List[List[float]] = []
        g_frac: List[float] = []
        g_slot: List[int] = []
        g_task: List[int] = []
        slots: List[SlotId] = []
        slot_of: Dict[SlotId, int] = {}
        sink_groups: List[List[int]] = []
        self.row_spans: List[Tuple[int, int]] = []
        self.group_spans: List[Tuple[int, int]] = []
        self._sim_slot_rows: List[np.ndarray] = []
        row_off = grp_off = 0
        for sim in self.sims:
            gi = sim.gi
            for lo, hi in gi.row_slices():
                row_slices.append((lo + grp_off, hi + grp_off))
            for row in range(len(gi.tasks)):
                in_edges.append([(src + row_off, mult)
                                 for src, mult in gi.in_edges[row]])
                hops.append(list(sim._hops[row]))
            sim_rows = []
            for s in gi.slots:
                if s not in slot_of:
                    slot_of[s] = len(slots)
                    slots.append(s)
                sim_rows.append(slot_of[s])
            self._sim_slot_rows.append(np.asarray(sim_rows, dtype=int))
            remap = np.asarray(sim_rows, dtype=int)
            g_slot.extend((remap[gi.g_slot]).tolist() if gi.n_groups else [])
            g_task.extend((gi.g_task + row_off).tolist())
            g_frac.extend(gi.g_frac.tolist())
            sink_groups.append([r + row_off for r in sim._sink_rows])
            self.row_spans.append((row_off, row_off + len(gi.tasks)))
            self.group_spans.append((grp_off, grp_off + gi.n_groups))
            row_off += len(gi.tasks)
            grp_off += gi.n_groups
        self.spec = _SweepSpec(
            row_slices=row_slices, in_edges=in_edges, hops=hops,
            g_frac=np.asarray(g_frac, dtype=float),
            g_slot=np.asarray(g_slot, dtype=int),
            g_task=np.asarray(g_task, dtype=int),
            slots=slots, sink_groups=sink_groups)

    # -- raw engine dispatch --------------------------------------------------
    def sweep_raw(self, omegas_list: Sequence[Sequence[float]], *,
                  duration: float = 60.0, dt: float = 0.05,
                  warmup: float = 5.0, latency_sample_every: float = 0.25,
                  engine: str = "numpy") -> SweepRaw:
        if engine not in ENGINES:
            raise ValueError(f"unknown simulator engine {engine!r}")
        if len(omegas_list) != len(self.sims):
            raise ValueError("one omega vector per co-simulated dataflow")
        omegas = [np.asarray(w, dtype=float) for w in omegas_list]
        K = len(omegas[0])
        if any(len(w) != K for w in omegas):
            raise ValueError("all sweeps must share one rate-grid length")
        with _obs_span("cosim.inputs"):
            caps = np.concatenate([
                effective_capacity_matrix(sim.gi, w,
                                          cpu_penalty=sim.cpu_penalty)
                for sim, w in zip(self.sims, omegas)], axis=0)
            src_rate = np.concatenate([
                sim.gi.betas[:, None] * w[None, :]
                for sim, w in zip(self.sims, omegas)], axis=0)
        steps, sample_every, s0 = _sweep_steps(duration, dt, warmup,
                                               latency_sample_every)
        if engine == "scan":
            queues, busy, served, realized, lat = self._run_scan(
                caps, src_rate, steps, sample_every, s0, dt)
        else:
            queues, busy, served, realized, lat = _sweep_numpy(
                self.spec, caps, src_rate, steps, sample_every, s0, dt)
        sample_times = np.arange(0, steps, sample_every) * dt
        return SweepRaw(queues=queues, busy=busy, served=served,
                        realized=realized, latency=lat,
                        sample_times=sample_times, steps=steps, s0=s0,
                        dt=dt, window=max(steps - s0, 1) * dt)

    def simulate(self, omegas_list: Sequence[Sequence[float]], *,
                 duration: float = 60.0, dt: float = 0.05,
                 warmup: float = 5.0, latency_sample_every: float = 0.25,
                 engine: str = "numpy") -> List[List[SimResult]]:
        """Per-simulator lists of :class:`SimResult`, one per swept rate."""
        omegas = [np.asarray(w, dtype=float) for w in omegas_list]
        raw = self.sweep_raw(omegas, duration=duration, dt=dt, warmup=warmup,
                             latency_sample_every=latency_sample_every,
                             engine=engine)
        return self.results_from_raw(omegas, raw)

    def results_from_raw(self, omegas_list: Sequence[np.ndarray],
                         raw: SweepRaw) -> List[List[SimResult]]:
        """Post-process one :class:`SweepRaw` into per-simulator results
        (split out of :meth:`simulate` so callers that also need the raw
        state — e.g. fleet resource studies — run the engine once).  The
        warm-up cut is derived from the window baked into ``raw`` (its
        ``s0``), so latency stats and busy fractions share one notion of
        warm-up — they only diverge in the explicit short-run fallback
        below, where too few post-warmup samples exist for a slope fit and
        the whole latency series is judged instead."""
        omegas = [np.asarray(w, dtype=float) for w in omegas_list]
        # stability: slope of latencies past warm-up (§5.1 criterion).  The
        # short-run path is explicit: with fewer than 3 post-warmup samples a
        # slope fit is meaningless, so the WHOLE series (warmup included) is
        # judged — and ``latency_samples`` reports exactly the judged window.
        times = raw.sample_times
        warm_time = raw.s0 * raw.dt
        k0 = (int(np.argmax(times >= warm_time - 1e-12))
              if np.any(times >= warm_time - 1e-12) else 0)
        if len(times) - k0 < 3:
            k0 = 0
        interval = (times[1] - times[0]) if len(times) > 1 else 1.0
        out: List[List[SimResult]] = []
        for i, sim in enumerate(self.sims):
            g_lo, g_hi = self.group_spans[i]
            tail = raw.latency[k0:, i, :]
            # per-sample slope -> seconds of latency per second of run time
            slopes = _slope_columns(tail) / interval
            slot_rows = self._sim_slot_rows[i]
            results: List[SimResult] = []
            for k in range(tail.shape[1]):
                col = tail[:, k]
                mean_lat = float(col.mean()) if col.size else 0.0
                p99 = float(np.sort(col)[int(0.99 * (col.size - 1))]) \
                    if col.size else 0.0
                results.append(SimResult(
                    omega=float(omegas[i][k]),
                    stable=bool(slopes[k] <= STABLE_SLOPE_PER_S),
                    latency_slope=float(slopes[k]), mean_latency=mean_lat,
                    p99_latency=p99, latency_samples=col.tolist(),
                    queue_total=float(raw.queues[g_lo:g_hi, k].sum()),
                    slot_busy={sim.gi.slots[j]:
                               float(raw.busy[s, k] / raw.window)
                               for j, s in enumerate(slot_rows)},
                ))
            out.append(results)
        return out

    # -- the jitted lax.scan kernel -------------------------------------------
    def _run_scan(self, caps: np.ndarray, src_rate: np.ndarray, steps: int,
                  sample_every: int, s0: int, dt: float):
        import jax

        from ..jaxenv import x64
        spec = self.spec
        # host arrays carry their dtypes; the jitted call converts them
        # inside the x64 scope, so none of them narrows to float32
        f64 = np.float64
        with _obs_span("cosim.inputs"):
            args = (caps.astype(f64), src_rate.astype(f64), f64(dt),
                    spec.g_frac.astype(f64), spec.g_slot.astype(np.int32),
                    np.asarray(self._hops_flat, dtype=f64))
        with _obs_span("cosim.launch"):
            fn = get_scan_kernel(spec.row_slices, spec.in_edges,
                                 spec.sink_groups, len(spec.slots))
            with x64():
                out = fn(*args, steps=steps, sample_every=sample_every,
                         s0=s0)
        with _obs_span("cosim.wait"):
            jax.block_until_ready(out)
        with _obs_span("cosim.fetch"):
            return tuple(np.asarray(a) for a in out)


# ---------------------------------------------------------------------------
# Engines.
# ---------------------------------------------------------------------------

def _sweep_numpy(spec: _SweepSpec, caps: np.ndarray, src_rate: np.ndarray,
                 steps: int, sample_every: int, s0: int, dt: float):
    """Reference tick loop: Python over ticks/rows, numpy over ``(., K)``."""
    T, G = spec.n_rows, spec.n_groups
    S = len(spec.slots)
    K = caps.shape[1]
    cap_pos = caps > 0
    safe_caps = np.where(cap_pos, caps, 1.0)
    queues = np.zeros((G, K))
    busy = np.zeros((S, K))
    served_acc = np.zeros((G, K))
    realized = np.zeros((T, K))
    served = np.zeros((G, K))
    lat: List[np.ndarray] = []
    for step in range(steps):
        # per-task realized output rate this tick, in topo order
        # (upstream being overloaded throttles downstream arrivals)
        for row in range(T):
            edges = spec.in_edges[row]
            if not edges:
                in_rate = src_rate[row]
            else:
                in_rate = np.zeros(K)
                for src, mult in edges:
                    in_rate = in_rate + realized[src] * mult
            lo, hi = spec.row_slices[row]
            if lo == hi:
                realized[row] = in_rate
                continue
            arr = in_rate[None, :] * spec.g_frac[lo:hi, None]
            q_len = queues[lo:hi] + arr * dt
            served[lo:hi] = np.minimum(q_len, caps[lo:hi] * dt)
            queues[lo:hi] = q_len - served[lo:hi]
            realized[row] = served[lo:hi].sum(axis=0) / dt
        if step >= s0:
            np.add.at(busy, spec.g_slot,
                      np.where(cap_pos, served / safe_caps, 0.0))
            served_acc += served
        if step % sample_every == 0:
            lat.append(_path_latency_np(spec, queues, caps))
    n_out = len(spec.sink_groups)
    lat_arr = (np.stack(lat) if lat else np.zeros((0, n_out, K)))
    return queues, busy, served_acc, realized, lat_arr


def _path_latency_np(spec: _SweepSpec, queues: np.ndarray,
                     caps: np.ndarray) -> np.ndarray:
    """Expected end-to-end latency per sweep column and output group: per
    task, the routing-weighted queue wait + service time, plus hop latency
    along the longest (source -> sink) DAG path."""
    K = queues.shape[1]
    contrib = np.where(caps > 0,
                       spec.g_frac[:, None] * (queues + 1.0)
                       / np.where(caps > 0, caps, 1.0),
                       0.0)
    per_task = np.zeros((spec.n_rows, K))
    np.add.at(per_task, spec.g_task, contrib)
    best = np.zeros_like(per_task)
    for row in range(spec.n_rows):
        edges = spec.in_edges[row]
        if not edges:
            best[row] = per_task[row]
            continue
        up = np.full(K, -np.inf)
        for (src, _), hop in zip(edges, spec.hops[row]):
            up = np.maximum(up, best[src] + hop)
        best[row] = per_task[row] + up
    out = np.zeros((len(spec.sink_groups), K))
    for i, rows in enumerate(spec.sink_groups):
        if rows:
            out[i] = np.max(best[rows], axis=0)
    return out


def _make_scan_kernel(row_slices, in_edges, sink_groups, n_slots: int,
                      *, batched: bool = False):
    """Build the jitted ``lax.scan`` sweep engine for one spec *structure*.

    The task loop is unrolled at trace time (T is small and static): each
    row's group block is a static slice of the ``(G, K)`` state and in-edge
    gathers are baked-in constants.  Placement data — routing fractions,
    group→slot ids, per-edge hop latencies — arrives as traced arrays, so
    every mapping with the same structure (same per-row group spans) reuses
    this kernel; the per-tick scatter onto slots uses ``.at[g_slot].add``.
    Latency rows are written into an ``(n_samples, ...)`` carry buffer only
    on sample ticks (``lax.cond``), and final realized rates ride along in
    the carry.  Compiled once per (K, steps, sample_every, s0) shape; ``dt``
    stays a traced scalar.

    With ``batched=True`` the kernel is ``jax.vmap``-ed over a leading
    *candidate* axis on ``caps``/``g_frac``/``g_slot``/``hops`` (``src_rate``
    and ``dt`` are shared), which is how the mapper search evaluates a whole
    pool of candidate mappings of one DAG in a single XLA program.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    row_slices = [(int(lo), int(hi)) for lo, hi in row_slices]
    in_edges = [[(int(s), float(m)) for s, m in e] for e in in_edges]
    sink_groups = [[int(r) for r in rows] for rows in sink_groups]
    T = len(row_slices)
    G = max((hi for _, hi in row_slices), default=0)
    S = int(n_slots)
    n_out = len(sink_groups)
    # static offsets of each row's in-edges within the flat hops array
    hop_off = np.concatenate(
        [[0], np.cumsum([len(e) for e in in_edges])]).astype(int)
    g_task_c = np.zeros(G, dtype=np.int32)
    for row, (lo, hi) in enumerate(row_slices):
        g_task_c[lo:hi] = row

    def kernel(caps, src_rate, dt, g_frac, g_slot, hops,
               *, steps, sample_every, s0):
        K = caps.shape[1]
        cap_pos = caps > 0
        safe_caps = jnp.where(cap_pos, caps, 1.0)
        caps_dt = caps * dt
        frac = g_frac[:, None]
        g_slot_i = g_slot.astype(jnp.int32)

        def path_latency(queues):
            contrib = jnp.where(cap_pos, frac * (queues + 1.0) / safe_caps,
                                0.0)
            per_task = jnp.zeros((T, K), caps.dtype) \
                .at[jnp.asarray(g_task_c)].add(contrib)  # lint: ok JAX104 - structural constant, part of the kernel cache key
            best: List = [None] * T
            for row in range(T):
                if not in_edges[row]:
                    best[row] = per_task[row]
                    continue
                up = None
                for j, (src, _) in enumerate(in_edges[row]):
                    cand = best[src] + hops[hop_off[row] + j]
                    up = cand if up is None else jnp.maximum(up, cand)
                best[row] = per_task[row] + up
            rows_out = []
            for rows in sink_groups:
                if not rows:
                    rows_out.append(jnp.zeros(K, caps.dtype))
                    continue
                acc = best[rows[0]]
                for r in rows[1:]:
                    acc = jnp.maximum(acc, best[r])
                rows_out.append(acc)
            return jnp.stack(rows_out)

        n_samples = -(-steps // sample_every) if steps > 0 else 0

        def tick(carry, step):
            queues, busy, served_acc, _, lat_buf = carry
            realized: List = [None] * T
            q_blocks: List = []
            s_blocks: List = []
            for row in range(T):
                edges = in_edges[row]
                if not edges:
                    in_rate = src_rate[row]
                else:
                    in_rate = realized[edges[0][0]] * edges[0][1]
                    for src, mult in edges[1:]:
                        in_rate = in_rate + realized[src] * mult
                lo, hi = row_slices[row]
                if lo == hi:
                    realized[row] = in_rate
                    continue
                arr = in_rate[None, :] * frac[lo:hi]
                q_len = queues[lo:hi] + arr * dt
                srv = jnp.minimum(q_len, caps_dt[lo:hi])
                q_blocks.append(q_len - srv)
                s_blocks.append(srv)
                realized[row] = srv.sum(axis=0) / dt
            if q_blocks:
                queues = jnp.concatenate(q_blocks, axis=0)
                srv_all = jnp.concatenate(s_blocks, axis=0)
            else:
                srv_all = jnp.zeros_like(queues)
            in_window = step >= s0
            busy_inc = jnp.where(cap_pos, srv_all / safe_caps, 0.0)
            busy = busy.at[g_slot_i].add(
                jnp.where(in_window, busy_inc, 0.0))
            served_acc = served_acc + jnp.where(in_window, srv_all, 0.0)
            # only sample ticks write a latency row, so the carry buffer is
            # (n_samples, ...) — not one row per tick
            lat_buf = lax.cond(
                step % sample_every == 0,
                lambda buf: buf.at[step // sample_every]
                .set(path_latency(queues)),
                lambda buf: buf, lat_buf)
            realized_arr = jnp.stack(realized)
            return (queues, busy, served_acc, realized_arr, lat_buf), None

        init = (jnp.zeros((G, K), caps.dtype),
                jnp.zeros((S, K), caps.dtype),
                jnp.zeros((G, K), caps.dtype),
                jnp.zeros((T, K), caps.dtype),
                jnp.zeros((n_samples, n_out, K), caps.dtype))
        (queues, busy, served_acc, realized, lat), _ = lax.scan(
            tick, init, jnp.arange(steps))
        return queues, busy, served_acc, realized, lat

    if not batched:
        # lint: ok JAX110 - construction memoized by get_scan_kernel's cache
        return jax.jit(kernel, static_argnames=("steps", "sample_every",
                                                "s0"))

    def batched_kernel(caps, src_rate, dt, g_frac, g_slot, hops,
                       *, steps, sample_every, s0):
        def one(c, f, s, h):
            return kernel(c, src_rate, dt, f, s, h, steps=steps,
                          sample_every=sample_every, s0=s0)
        return jax.vmap(one)(caps, g_frac, g_slot, hops)

    # lint: ok JAX110 - construction memoized by get_scan_kernel's cache
    return jax.jit(batched_kernel, static_argnames=("steps", "sample_every",
                                                    "s0"))


def _slope_columns(samples: np.ndarray) -> np.ndarray:
    """Least-squares slope of each column vs sample index (vectorized
    :func:`latency_slope`) — per *sample*; divide by the sample interval to
    get the per-second slope the stability criterion uses."""
    n = samples.shape[0]
    if n < 2:
        return np.zeros(samples.shape[1] if samples.ndim == 2 else 1)
    x = np.arange(n) - (n - 1) / 2.0
    den = float((x ** 2).sum())
    return x @ (samples - samples.mean(axis=0)) / den


def measured_resources(dag: Dataflow, alloc: Allocation, mapping: ThreadMapping,
                       models: ModelLibrary, omega: float,
                       policy: RoutingPolicy = RoutingPolicy.SHUFFLE,
                       *, seed: int = 0, noise: float = 0.06
                       ) -> Tuple[Dict[int, float], Dict[int, float]]:
    """Per-VM 'actual' CPU%/mem% at rate omega.

    The actual usage differs from the §8.5 prediction because (a) routing
    skew sends groups more/less than their share — captured here by the
    fluid routing fractions — and (b) real resource draw is noisy; a small
    multiplicative noise term models the measurement scatter of Figs. 11-12.
    """
    rng = random.Random(seed)
    rates = dag.get_rates(omega)
    groups = slot_groups(mapping, alloc)
    caps = effective_capacities(dag, alloc, mapping, models)
    vm_cpu: Dict[int, float] = {vm.id: 0.0 for vm in mapping.vms}
    vm_mem: Dict[int, float] = {vm.id: 0.0 for vm in mapping.vms}
    for task, g in groups.items():
        kind = alloc.tasks[task].kind
        model = models[kind]
        incoming = group_rates(task, kind, rates[task], g, models, policy)
        for slot, q in g.items():
            cap = caps[task][slot]
            served = min(incoming[slot], cap)
            peak = model.I(q)
            frac_used = 1.0 if peak <= 0 else min(1.0, served / peak)
            jit_c = 1.0 + rng.uniform(-noise, noise)
            jit_m = 1.0 + rng.uniform(-noise, noise)
            vm_cpu[slot.vm] += model.C(q) * frac_used * jit_c
            vm_mem[slot.vm] += model.M(q) * frac_used * jit_m
    return vm_cpu, vm_mem

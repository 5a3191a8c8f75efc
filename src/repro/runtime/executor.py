"""Streaming executor: enacts a planned Schedule on real JAX devices.

Each resource *slot* of the schedule is pinned to a JAX device, round-robin
over the executor's device list (slot k -> ``devices[k % n]``, default
``jax.devices()``; with ``--xla_force_host_platform_device_count`` the CPU
exposes many devices, so a multi-VM schedule demonstrably runs with the
same thread->slot structure the mapper produced).  Every routed part is
placed on its slot's device with ``jax.device_put`` before the slot's
jitted operator runs, so the operator executes where its input lives.
Tuples flow as micro-batch frames in DAG topological order; at each task
the frame is routed over the task's per-slot thread groups (shuffle =
thread-proportional, slot-aware = capacity-proportional), processed by the
slot-pinned jitted operator, and the results interleave downstream — the
Storm execution model of §2.  A device frame cut into two or more parts is
cut by one compiled program, and the parts' outputs are joined by another,
so a task's route costs one launch each way whatever its keys and parts.
Every part of every task is launched without a host wait (JAX orders the
work on each device, a ``device_put`` of an array not yet ready included),
and the frame blocks once per sink reached.

A stateful kind (``operators.KEYED``) keeps a state table on the device of
each of its slots, and its tuples are routed by key (Storm's fields
grouping): tuple i goes to thread ``hash32(key_i) mod Q`` and so to that
thread's slot, where the whole frame arrives with every other slot's rows
masked invalid.  Parts keep the frame's shape, so the route compiles once
whatever the mix of keys; one compiled program finds the owners and cuts
the parts, and one takes each row back from its owner's output.  A kind
without a key sends the frame whole to the task's first thread (global
grouping), and a schedule that maps it to two or more slots is refused.  A kind in ``operators.MERGES`` reads the union of its in-edges'
outputs, each row tagged with its in-edge (``branch``); every other kind
reads its first in-edge that produced an output.  :meth:`StreamExecutor.rebind`
carries each key's state to its new owner.

Robustness machinery (the chaos-hardened enactment layer):

* **per-frame operator retry** — a failing operator attempt is retried with
  exponential backoff up to :attr:`RobustnessPolicy.max_retries` times,
  bounded by the frame deadline;
* **frame-timeout watchdog** — a frame whose processing (stalls included)
  exceeds :attr:`RobustnessPolicy.frame_deadline_intervals` × the frame
  interval is abandoned and counted, so one wedged operator cannot hang the
  run;
* **load shedding** — a frame arriving when the executor is already behind
  by more than :attr:`RobustnessPolicy.shed_backlog_frames` frames is shed
  (graceful degradation instead of unbounded queue growth);
* **circuit breaker** — a slot failing :attr:`RobustnessPolicy.breaker_threshold`
  consecutive frames trips its VM: the VM's parts are skipped and the id is
  queued for escalation (:meth:`StreamExecutor.take_escalations`) so the
  enactment layer can feed a synthetic ``VmFail`` back to the controller.

Faults are injected between routing and the operator invocation via an
optional :class:`~repro.runtime.chaos.FaultInjector`.  Timing runs on a
pluggable clock (:mod:`repro.runtime.stream`): under a
:class:`~repro.runtime.stream.VirtualClock`, operator costs come from the
performance-model tables (``truth`` — the measured "ground truth" library),
which makes whole chaos replays deterministic and sleep-free.

Measured per-(task, slot-group) service rates accumulate in the executor
and feed :mod:`repro.core.calibrate` — the measure→recalibrate loop.  Under
the wall clock a part's busy time is its host time in ``executor.launch``
plus a share of the frame's sink waits in proportion to that time; each
part's sample is held until the frame returns.

Telemetry (:mod:`repro.obs`, free while off): :meth:`process_frame` opens
an ``executor.frame`` span with one child span per stage of the frame
(``route``, ``keyroute``, ``merge``, ``place``, ``launch``, ``service``,
``gather``, ``sink_wait``), counts frames and tuples at the sites that
decide their fate, the compiled split and interleave launches
(``repro_executor_route_launches_total``), the host blocks
(``repro_executor_host_waits_total``), the host time of keyed routing, and
the bytes of state held and moved by rebinds.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dag import Dataflow, Routing
from ..core.perfmodel import ModelLibrary, latency_slope
from ..core.predictor import slot_groups
from ..core.routing import RoutingPolicy
from ..core.scheduler import Schedule
from ..obs import metrics as _obs_metrics
from ..obs.trace import span as _obs_span
from .chaos import FaultInjector, FaultKind, InjectedOperatorError
from .operators import KEYED, MERGES, OPERATORS, SERVICE_LATENCY, hash32
from .stream import MicroBatch, SyntheticSource, VirtualClock, WallClock

_FRAMES = _obs_metrics.counter(
    "repro_frames_total", "Micro-batch frames processed by executors.")
_FRAMES_SHED = _obs_metrics.counter(
    "repro_frames_shed_total", "Frames dropped by load shedding.")
_FRAMES_RETRIED = _obs_metrics.counter(
    "repro_frames_retried_total",
    "Operator invocations retried after transient errors.")
_FRAMES_TIMED_OUT = _obs_metrics.counter(
    "repro_frames_timed_out_total",
    "Frames killed by the frame-deadline watchdog.")
_FRAMES_FAILED = _obs_metrics.counter(
    "repro_frames_failed_total", "Frames that lost tuples past retry.")
_TUPLES_LOST = _obs_metrics.counter(
    "repro_tuples_lost_total",
    "Tuples of parts lost past retry or skipped on a tripped VM.")
_ROUTE_LAUNCHES = {
    stage: _obs_metrics.counter(
        "repro_executor_route_launches_total",
        "Compiled programs launched to split a frame over a task's slot "
        "groups or to interleave their outputs.", labels={"stage": stage})
    for stage in ("split", "interleave")}
_HOST_WAITS = _obs_metrics.counter(
    "repro_executor_host_waits_total",
    "Host blocks on device results inside process_frame: one per sink "
    "reached per frame.")
_KEYROUTE_SECONDS = _obs_metrics.counter(
    "repro_executor_keyroute_seconds_total",
    "Host seconds spent finding the owner thread of each tuple of a keyed "
    "task's frame and cutting the frame into its slots' masked parts.",
    unit="s")
_STATE_MOVED = _obs_metrics.counter(
    "repro_executor_state_moved_bytes_total",
    "Bytes of keyed state moved to a key's new owner slot by rebinds.",
    unit="bytes")


def _state_gauge(task: str):
    return _obs_metrics.gauge(
        "repro_executor_state_bytes",
        "Bytes of a stateful task's state held on its slots' devices.",
        unit="bytes", labels={"task": task})


@functools.partial(jax.jit, static_argnames="bounds")
def _split(arrays: Dict[str, jax.Array],
           bounds: Tuple[Tuple[int, int], ...]) -> List[Dict[str, jax.Array]]:
    """Every part ``[lo, hi)`` of a frame in one program (compiled once
    per cut and frame shape, shared by every executor)."""
    return [{k: v[lo:hi] for k, v in arrays.items()} for lo, hi in bounds]


@jax.jit
def _interleave(outs: List[Dict[str, jax.Array]]) -> Dict[str, jax.Array]:
    """The parts' outputs joined back along the tuple axis, every key in
    one program."""
    return {k: jnp.concatenate([o[k] for o in outs], axis=0)
            for k in outs[0]}


@functools.partial(jax.jit, static_argnames=("key", "threads"))
def _keyroute(arrays: Dict[str, jax.Array], key: str,
              threads: Tuple[int, ...]):
    """Each tuple's owner, as the index of its slot in ``threads`` (the
    task's thread count per slot, in slot order), and one part per slot:
    the whole frame with every row the slot does not own masked invalid."""
    n = arrays[key].shape[0]
    thread = hash32(arrays[key]) % jnp.uint32(sum(threads))
    owner = jnp.searchsorted(jnp.cumsum(jnp.asarray(threads, jnp.uint32)),
                             thread, side="right").astype(jnp.int32)
    valid = arrays.get("valid", jnp.ones((n,), bool))
    return owner, [{**arrays, "valid": valid & (owner == k)}
                   for k in range(len(threads))]


def _rows_where(mask, v):
    return jnp.reshape(mask, mask.shape + (1,) * (v.ndim - 1))


@functools.partial(jax.jit, static_argnames="parts")
def _keymerge(outs: List[Dict[str, jax.Array]], owner: jax.Array,
              parts: Tuple[int, ...]) -> Dict[str, jax.Array]:
    """Each row from its owner's output; ``parts`` names the slot index of
    each output, and a row whose owner's part was lost is zero and not
    valid."""
    merged = {k: jnp.zeros_like(v) for k, v in outs[0].items()}
    for out, k in zip(outs, parts):
        mine = owner == k
        merged = {f: jnp.where(_rows_where(mine, v), out[f], v)
                  for f, v in merged.items()}
    return merged


@functools.partial(jax.jit, static_argnames="branches")
def _union(ins: List[Dict[str, jax.Array]],
           branches: Tuple[int, ...]) -> Dict[str, jax.Array]:
    """The rows of every input one after another, each field of any input
    present (zeros in the rows of an input without it), ``branch`` the
    input's in-edge index and ``valid`` its mask (all valid without one)."""
    sizes = [next(iter(x.values())).shape[0] for x in ins]
    like = {}
    for x in ins:
        for f, v in x.items():
            like.setdefault(f, v)
    out = {f: jnp.concatenate([
        x[f] if f in x else jnp.zeros((n,) + v.shape[1:], v.dtype)
        for x, n in zip(ins, sizes)]) for f, v in like.items()}
    out["valid"] = jnp.concatenate([x.get("valid", jnp.ones((n,), bool))
                                    for x, n in zip(ins, sizes)])
    out["branch"] = jnp.concatenate([jnp.full((n,), b, jnp.int32)
                                     for b, n in zip(branches, sizes)])
    return out


def _slot_groups(schedule: Schedule) -> Dict[str, Dict]:
    """The schedule's slot groups per task.  A stateful kind without a key
    (global grouping) holds one state table and takes every tuple on its
    first thread, so a schedule that spreads it over two or more slots is
    refused: the planner would count the other slots' threads as capacity
    that never works."""
    groups = slot_groups(schedule.mapping, schedule.allocation)
    for task, g in groups.items():
        keyed = KEYED.get(schedule.allocation.tasks[task].kind)
        if keyed is not None and keyed.key is None and len(g) > 1:
            raise ValueError(
                f"task {task!r} is globally grouped but mapped to {len(g)} "
                "slots; its threads must share one slot")
    return groups


def _kind_fn(kind: str):
    return KEYED[kind].fn if kind in KEYED else OPERATORS[kind]


def _slot_order(g) -> List:
    return sorted(g, key=lambda s: (s.vm, s.slot))


def _row_owners(g, keyed) -> np.ndarray:
    """Index (in slot order) of the slot that owns each state row of a
    keyed task with thread counts ``g``: the routing of ``_keyroute``
    applied to the row's key."""
    if keyed.key is None:
        return np.zeros(keyed.rows, np.int64)
    threads = np.array([g[s] for s in _slot_order(g)], np.uint32)
    thread = hash32(np.arange(keyed.rows, dtype=np.uint32), xp=np) \
        % np.uint32(threads.sum())
    return np.searchsorted(np.cumsum(threads), thread, side="right")


@dataclasses.dataclass
class RobustnessPolicy:
    """Retry / watchdog / shedding / breaker knobs of the live executor."""

    max_retries: int = 2                  # extra attempts per (frame, part)
    backoff_base: float = 0.004           # s; doubles per retry
    frame_deadline_intervals: float = 8.0  # watchdog: x frame interval
    shed_backlog_frames: float = 4.0      # shed when lag exceeds this many
    breaker_threshold: int = 3            # consecutive slot failures to trip


@dataclasses.dataclass
class ExecutionReport:
    omega: float
    frames: int
    tuples: int
    wall_seconds: float
    throughput: float            # tuples/s actually sustained end-to-end
    mean_latency: float
    p99_latency: float
    latency_slope: float
    stable: bool
    device_frame_counts: Dict[str, int]
    #: why ``stable`` is False ("" when stable): degenerate measurement
    #: windows report explicitly instead of crashing or silently passing
    stable_reason: str = ""
    frames_shed: int = 0         # load-shedding drops (faulted drops included)
    frames_timed_out: int = 0    # watchdog abandons
    frames_failed: int = 0       # frames that lost tuples to operator failure
    retries: int = 0             # operator attempts retried
    tuples_lost: int = 0         # tuples dropped by failed/skipped parts
    escalated_vms: Tuple[int, ...] = ()   # VMs the breaker tripped this run


@dataclasses.dataclass
class RebindInfo:
    """What :meth:`StreamExecutor.rebind` changed: the enactment delta."""

    kept_slots: List = dataclasses.field(default_factory=list)
    restarted_slots: List = dataclasses.field(default_factory=list)
    transplanted: Dict = dataclasses.field(default_factory=dict)  # old->new
    reused_ops: int = 0
    fresh_ops: int = 0
    state_moved_bytes: int = 0   # keyed state carried to new owners


class _FrameTimeout(RuntimeError):
    """Internal: the watchdog fired mid-frame."""


class StreamExecutor:
    """Frame-at-a-time executor (demo-scale faithful enactment): a frame's
    parts are dispatched without a wait, and the frame returns once its
    sink outputs are ready.

    ``clock`` selects wall vs virtual time; ``truth`` is the model library
    whose tables price operator work under a virtual clock (defaults to
    ``models`` — pass the *actual* measured profile to emulate a cluster
    whose reality drifted from the planner's tables); ``faults`` injects a
    :class:`~repro.runtime.chaos.FaultPlan` slice; ``robustness`` tunes the
    retry/watchdog/shedding/breaker machinery; ``devices`` is the list
    slots are pinned to round-robin (default ``jax.devices()``).
    """

    def __init__(self, schedule: Schedule, models: ModelLibrary,
                 *, policy: RoutingPolicy = RoutingPolicy.SHUFFLE,
                 faults: Optional[FaultInjector] = None,
                 robustness: Optional[RobustnessPolicy] = None,
                 clock=None, truth: Optional[ModelLibrary] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        self.schedule = schedule
        self.models = models
        self.truth = truth if truth is not None else models
        self.policy = policy
        self.faults = faults
        self.robust = robustness if robustness is not None else RobustnessPolicy()
        self.clock = clock if clock is not None else WallClock()
        self.dag = schedule.dag
        self.groups = _slot_groups(schedule)
        self._devices = list(devices) if devices is not None \
            else jax.devices()
        if not self._devices:
            raise ValueError("StreamExecutor needs at least one device")
        self._device_counter = 0
        # slot -> device pinning (stable order over VMs then slots)
        self.slot_device = {}
        for slot in schedule.mapping.slots():
            self.slot_device[slot] = self._next_device()
        # jitted operator per (task, slot); it runs on the device its
        # input was placed on (see _invoke_part)
        self._ops = {}
        for task, g in self.groups.items():
            fn = _kind_fn(schedule.allocation.tasks[task].kind)
            for slot in g:
                self._ops[(task, slot)] = jax.jit(fn)  # lint: ok JAX101 - one-time __init__ cache, each (task, slot) jitted once
        #: (task, slot) -> state table of a stateful task, on the slot's
        #: device for the lifetime of the group
        self._state: Dict[Tuple[str, object], Dict[str, jax.Array]] = {}
        self._carry_state({}, {}, {})
        self._frame_count = defaultdict(int)
        # robustness state (survives rebinds for surviving slots)
        self._consecutive_failures: Dict = defaultdict(int)
        self.tripped_vms: Set[int] = set()
        self._pending_escalations: List[int] = []
        # measured service accumulation: (task, slot, threads) -> [tuples,
        # busy_s] — keyed by the thread count at invocation time, so
        # samples from before and after a rebind never mix thread counts
        self._measured: Dict[Tuple[str, object, int], List[float]] = {}
        #: this frame's samples, held until it returns: (task, slot,
        #: threads, tuples, busy_s before the sink waits' share, slowdown)
        self._samples: List[Tuple[str, object, int, float, float,
                                  float]] = []
        self._run_counters: Dict[str, int] = {}
        #: frames consumed across ALL runs — the fault plan's frame axis
        #: continues across measurement windows (chaos determinism)
        self.frames_seen = 0
        #: sink name -> output arrays of the last frame that reached the
        #: sinks (what the dataflow emitted; compared across device sets)
        self.last_sink_outputs: Dict[str, Dict[str, jax.Array]] = {}

    # -- device bookkeeping ----------------------------------------------------
    def _next_device(self):
        dev = self._devices[self._device_counter % len(self._devices)]
        self._device_counter += 1
        return dev

    # -- enactment deltas ------------------------------------------------------
    def rebind(self, new_schedule: Schedule,
               transplants: Optional[Dict] = None) -> RebindInfo:
        """Apply a controller delta in place: reuse the jitted operator of
        every (task, slot) group the new schedule keeps, transplant the ops
        of redirected slots (``transplants``: failed slot -> replacement
        slot — the ``VmFail`` repair path, which inherits the old slot's
        device pin so the compiled executable carries over verbatim), and
        jit fresh only for genuinely new groups.  A schedule the executor
        refuses (:func:`_slot_groups`) raises before anything changes.
        """
        groups = _slot_groups(new_schedule)
        old_ops = self._ops
        old_groups = self.groups
        old_devices = dict(self.slot_device)
        transplants = dict(transplants or {})
        reverse = {new: old for old, new in transplants.items()}
        self.schedule = new_schedule
        self.dag = new_schedule.dag
        self.groups = groups
        # device pins: keep surviving slots, inherit across transplants
        # (the replacement slot takes the failed slot's device so the
        # compiled executable can carry over verbatim), round-robin fresh
        live_slots = set(new_schedule.mapping.slots())
        self.slot_device = {s: d for s, d in old_devices.items()
                            if s in live_slots}
        for slot in new_schedule.mapping.slots():
            if slot in self.slot_device:
                continue
            src = reverse.get(slot)
            if src is not None and src in old_devices:
                self.slot_device[slot] = old_devices[src]
            else:
                self.slot_device[slot] = self._next_device()

        info = RebindInfo()
        self._ops = {}
        kept: Set = set()
        restarted: Set = set()
        for task, g in self.groups.items():
            fn = _kind_fn(new_schedule.allocation.tasks[task].kind)
            for slot in g:
                key = (task, slot)
                if key in old_ops:
                    self._ops[key] = old_ops[key]
                    info.reused_ops += 1
                    kept.add(slot)
                    continue
                # transplant: the redirected old slot ran the same task
                # group on the device this slot just inherited
                old_slot = reverse.get(slot)
                if (old_slot is not None and (task, old_slot) in old_ops
                        and self.slot_device[slot]
                        is old_devices.get(old_slot)):
                    self._ops[key] = old_ops[(task, old_slot)]
                    info.reused_ops += 1
                    info.transplanted[old_slot] = slot
                    restarted.add(slot)
                    continue
                self._ops[key] = jax.jit(fn)  # lint: ok JAX101 - rebind jits each new (task, slot) once
                info.fresh_ops += 1
                restarted.add(slot)
        info.state_moved_bytes = self._carry_state(old_groups, self._state,
                                                   reverse)
        info.kept_slots = sorted(kept, key=lambda s: (s.vm, s.slot))
        info.restarted_slots = sorted(restarted,
                                      key=lambda s: (s.vm, s.slot))
        # breaker state: a VM no longer in the schedule was repaired away
        live_vms = {vm.id for vm in new_schedule.vms}
        self.tripped_vms &= live_vms
        self._consecutive_failures = defaultdict(int, {
            s: n for s, n in self._consecutive_failures.items()
            if s in live_slots})
        return info

    def _carry_state(self, old_groups, old_state, reverse) -> int:
        """Give every (task, slot) group of a stateful task its table: a
        surviving slot keeps its own, a transplanted slot inherits its old
        slot's, a new slot starts fresh; then each state row whose owner
        changed is copied from its old owner's table.  Returns the bytes
        moved."""
        moved = 0
        self._state = {}
        for task, g in self.groups.items():
            keyed = KEYED.get(self.schedule.allocation.tasks[task].kind)
            if keyed is None or not g:
                continue
            slots = _slot_order(g)
            new_owner = _row_owners(g, keyed)
            og = old_groups.get(task) or {}
            old_slots = _slot_order(og)
            old_owner = _row_owners(og, keyed) if og and all(
                (task, o) in old_state for o in old_slots) else None
            for i, slot in enumerate(slots):
                dev = self.slot_device[slot]
                src = slot if (task, slot) in old_state else reverse.get(slot)
                table = old_state.get((task, src))
                if table is None:
                    table = jax.device_put(keyed.init(), dev)
                if old_owner is None:
                    self._state[(task, slot)] = table
                    continue
                for j, old in enumerate(old_slots):
                    rows = np.flatnonzero((new_owner == i) & (old_owner == j))
                    if old == src or rows.size == 0:
                        continue
                    with _obs_span("executor.state_move"):
                        got = jax.device_put(
                            {k: v[rows] for k, v in
                             old_state[(task, old)].items()}, dev)
                        table = {k: v.at[rows].set(got[k])
                                 for k, v in table.items()}
                    moved += sum(v.nbytes for v in got.values())
                self._state[(task, slot)] = table
            _state_gauge(task).set(sum(
                v.nbytes for s in slots
                for v in self._state[(task, s)].values()))
        _STATE_MOVED.inc(moved)
        return moved

    def take_escalations(self) -> List[int]:
        """VM ids the circuit breaker tripped since the last call — the
        enactment layer turns each into a synthetic ``VmFail`` event."""
        out, self._pending_escalations = self._pending_escalations, []
        return out

    # -- measurement -----------------------------------------------------------
    def measurements(self):
        """Measured per-(task, slot-group) service samples for
        :mod:`repro.core.calibrate` (kind, tau, tuples, busy seconds)."""
        from ..core.calibrate import TaskMeasurement
        out = []
        for (task, slot, q), (tuples, busy) in sorted(
                self._measured.items(),
                key=lambda kv: (kv[0][0], kv[0][1].vm, kv[0][1].slot,
                                kv[0][2])):
            if busy <= 0 or tuples <= 0:
                continue
            ta = self.schedule.allocation.tasks.get(task)
            if ta is None:
                continue
            out.append(TaskMeasurement(kind=ta.kind, task=task, tau=int(q),
                                       tuples=float(tuples),
                                       busy_seconds=float(busy)))
        return out

    def reset_measurements(self) -> None:
        self._measured = {}

    # -- routing ---------------------------------------------------------------
    def _weights(self, task: str) -> List[Tuple[object, float]]:
        g = self.groups[task]
        kind = self.schedule.allocation.tasks[task].kind
        model = self.models[kind]
        if self.policy is RoutingPolicy.SLOT_AWARE:
            w = {s: max(model.I(q), 1e-9) for s, q in g.items()}
        else:
            w = {s: float(q) for s, q in g.items()}
        total = sum(w.values())
        return [(s, w[s] / total) for s in sorted(w, key=lambda s: (s.vm, s.slot))]

    # -- execution ---------------------------------------------------------------
    def _virtual_cost(self, task: str, slot, n: int) -> float:
        """Model-implied processing time of ``n`` tuples on this slot group
        under the ``truth`` tables (the virtual clock's cost source)."""
        kind = self.schedule.allocation.tasks[task].kind
        q = self.groups[task][slot]
        cap = float(self.truth[kind].I(q))
        return n / max(cap, 1e-9)

    def _invoke_part(self, task: str, slot, part, frame_seq: int,
                     deadline_at: float, n: Optional[float] = None
                     ) -> Optional[Dict[str, jax.Array]]:
        """One routed part through retry/backoff, fault injection, and the
        circuit breaker.  Returns the operator output, or None when the
        part was lost (exhausted retries / tripped VM).  Only the modelled
        operator failure (:class:`InjectedOperatorError`) is retried; a
        JAX or XLA error (compile, out of memory, lost device) propagates.
        ``n`` is the part's tuple count where it is not the part's length
        (a keyed part is the whole frame, masked).  The operator is
        launched without a wait, so a device error surfaces at the frame's
        sink wait; a stateful group's table becomes the call's output at
        once.  The part's busy sample is held for :meth:`_record_busy`."""
        if n is None:
            n = next(iter(part.values())).shape[0]
        state = self._state.get((task, slot))
        fail_attempts = 0
        slow = 1.0
        if self.faults is not None:
            fail_attempts = self.faults.error_attempts(frame_seq, task, slot)
            slow = self.faults.slowdown(frame_seq, task, slot)
            stall = self.faults.stall(frame_seq, task, slot)
            if stall > 0:
                # a stalled attempt blocks until the watchdog budget runs out
                self.clock.sleep(min(stall,
                                     max(0.0, deadline_at - self.clock.now())
                                     + 1e-9))
        op = self._ops[(task, slot)]
        with _obs_span("executor.place"):
            part = jax.device_put(part, self.slot_device[slot])
        for attempt in range(self.robust.max_retries + 1):
            if self.clock.now() > deadline_at:
                raise _FrameTimeout(f"frame {frame_seq} exceeded its "
                                    f"deadline at task {task!r}")
            try:
                if attempt < fail_attempts:
                    raise InjectedOperatorError(
                        FaultKind.OPERATOR_ERROR
                        if not self.faults.is_crashed(slot.vm)
                        else FaultKind.VM_CRASH, task)
                with _obs_span("executor.launch"):
                    t0 = time.perf_counter()
                    out = op(part) if state is None else op(state, part)
                    busy = time.perf_counter() - t0
                if state is not None:
                    self._state[(task, slot)], out = out
                if self.clock.virtual:
                    busy = self._virtual_cost(task, slot, n)
                elif slow > 1.0:
                    # realize the slowdown in wall time too
                    self.clock.sleep(busy * (slow - 1.0))
                self._consecutive_failures[slot] = 0
                self._samples.append((task, slot, int(self.groups[task][slot]),
                                      n, busy, slow))
                return out
            except InjectedOperatorError:
                if attempt >= self.robust.max_retries:
                    break
                self._run_counters["retries"] = \
                    self._run_counters.get("retries", 0) + 1
                _FRAMES_RETRIED.inc()
                self.clock.sleep(self.robust.backoff_base * (2 ** attempt))
        # retries exhausted: part lost; feed the breaker
        self._run_counters["tuples_lost"] = \
            self._run_counters.get("tuples_lost", 0) + n
        _TUPLES_LOST.inc(n)
        self._consecutive_failures[slot] += 1
        if (self._consecutive_failures[slot] >= self.robust.breaker_threshold
                and slot.vm not in self.tripped_vms):
            self.tripped_vms.add(slot.vm)
            self._pending_escalations.append(slot.vm)
        return None

    def _record_busy(self, wait: float) -> None:
        """Add the frame's held samples to the measured busy time, sharing
        the sink waits ``wait`` (seconds on the executor's clock, so none
        under a virtual one) over the parts in proportion to their busy."""
        samples, self._samples = self._samples, []
        launched = sum(s[4] for s in samples)
        per_launch = wait / launched if launched > 0 else 0.0
        for task, slot, q, n, busy, slow in samples:
            acc = self._measured.setdefault((task, slot, q), [0.0, 0.0])
            acc[0] += n
            acc[1] += (busy + busy * per_launch) * slow

    def _run_task(self, task: str, arrays: Dict[str, jax.Array],
                  frame_seq: int = -1,
                  deadline_at: float = float("inf")) -> Dict[str, jax.Array]:
        g = self.groups.get(task)
        if not g:
            return arrays
        kind = self.schedule.allocation.tasks[task].kind
        keyed = KEYED.get(kind)
        owner = None
        if keyed is not None:
            routed, pieces, owner = self._route_keyed(task, keyed, arrays)
        else:
            routed, pieces = self._route(task, arrays)
        parts = {}
        lost = False
        for (slot, count), part in zip(routed, pieces):
            if slot.vm in self.tripped_vms:
                # breaker open: skip the dead VM's share entirely
                self._run_counters["tuples_lost"] = \
                    self._run_counters.get("tuples_lost", 0) + count
                _TUPLES_LOST.inc(count)
                lost = True
                continue
            out = self._invoke_part(task, slot, part, frame_seq, deadline_at,
                                    n=count)
            if out is None:
                lost = True
            else:
                parts[slot] = out
                self._frame_count[str(self.slot_device[slot])] += 1
        if lost:
            self._run_counters["frame_lost_tuples"] = 1
        if kind in SERVICE_LATENCY:
            # external service wait, parallelized over the task's threads
            q_total = sum(g.values())
            with _obs_span("executor.service"):
                self.clock.sleep(SERVICE_LATENCY[kind] / max(1, q_total))
        outs = list(parts.values())
        if not outs:
            return arrays if not lost else {}
        if owner is not None:
            # every row back from its owner's output, on the first slot
            with _obs_span("executor.gather"):
                slots = [s for s, _ in routed]
                home = self.slot_device[slots[0]]
                outs, owner = jax.device_put((outs, owner), home)
                return _keymerge(outs, owner, parts=tuple(
                    slots.index(s) for s in parts))
        if len(outs) == 1:
            return outs[0]
        # interleave across slots: gather to one device (the real tuple
        # movement between slots that Storm's network transfer performs)
        with _obs_span("executor.gather"):
            home = self.slot_device[next(iter(parts))]
            _ROUTE_LAUNCHES["interleave"].inc()
            return _interleave(jax.device_put(outs, home))

    def _route(self, task: str, arrays: Dict[str, jax.Array]):
        """``[(slot, tuples)]`` and the parts of a stateless task: the frame
        cut into contiguous non-empty parts in proportion to the weights."""
        n = next(iter(arrays.values())).shape[0]
        with _obs_span("executor.route"):
            weights = self._weights(task)
            # split the frame over slot groups: the non-empty parts
            # [lo, hi), in slot order
            routed, lo, acc = [], 0, 0.0
            for i, (slot, f) in enumerate(weights):
                acc += f
                hi = n if i == len(weights) - 1 else int(round(acc * n))
                if hi > lo:
                    routed.append((slot, lo, hi))
                lo = hi
            bounds = tuple((lo, hi) for _, lo, hi in routed)
            if len(bounds) > 1 and all(isinstance(v, jax.Array)
                                       for v in arrays.values()):
                # a frame on a device: every part in one program
                _ROUTE_LAUNCHES["split"].inc()
                pieces = _split(arrays, bounds=bounds)
            else:
                # one part is the whole frame; a host frame slices for free
                pieces = [{k: v[lo:hi] for k, v in arrays.items()}
                          for lo, hi in bounds]
        return [(slot, hi - lo) for slot, lo, hi in routed], pieces

    def _route_keyed(self, task: str, keyed, arrays: Dict[str, jax.Array]):
        """``[(slot, tuples)]``, the parts and each tuple's owner (None for
        one part) of a stateful task.  With a key and two or more slots
        every slot gets the whole frame with the rows it does not own
        masked, and its count is its expected share; otherwise the frame
        goes whole to the task's first slot."""
        g = self.groups[task]
        slots = _slot_order(g)
        n = next(iter(arrays.values())).shape[0]
        if keyed.key is None or len(slots) == 1:
            if "valid" not in arrays:
                arrays = {**arrays, "valid": np.ones(n, bool)}
            return [(slots[0], n)], [arrays], None
        t0 = time.perf_counter()
        with _obs_span("executor.keyroute"):
            threads = tuple(g[s] for s in slots)
            owner, pieces = _keyroute(arrays, key=keyed.key, threads=threads)
        _KEYROUTE_SECONDS.inc(time.perf_counter() - t0)
        q = sum(threads)
        return [(s, n * g[s] / q) for s in slots], pieces, owner

    def _merge(self, upstream: List[Tuple[int, Dict[str, jax.Array]]]
               ) -> Dict[str, jax.Array]:
        """The union of the in-edges' outputs ``(in-edge index, arrays)``,
        on the device of the first."""
        with _obs_span("executor.merge"):
            ins = [x for _, x in upstream]
            first = next(iter(ins[0].values()))
            if isinstance(first, jax.Array):
                ins = jax.device_put(ins, next(iter(first.devices())))
            return _union(ins, branches=tuple(i for i, _ in upstream))

    def process_frame(self, frame: MicroBatch, interval: float
                      ) -> Tuple[str, Optional[float]]:
        """Run one frame through the dataflow with the full robustness
        stack.  Returns ``(status, latency)`` with status one of ``"ok"``,
        ``"shed"``, ``"timeout"``, ``"failed"``; latency is set for ok
        frames only."""
        _FRAMES.inc()
        with _obs_span("executor.frame", seq=frame.seq):
            return self._process_frame(frame, interval)

    def _process_frame(self, frame: MicroBatch, interval: float
                       ) -> Tuple[str, Optional[float]]:
        now = self.clock.now()
        if interval > 0 and (now - frame.created) > \
                self.robust.shed_backlog_frames * interval:
            self._run_counters["frames_shed"] = \
                self._run_counters.get("frames_shed", 0) + 1
            _FRAMES_SHED.inc()
            return "shed", None
        if self.faults is not None:
            self.faults.crashed_vms(frame.seq,
                                    [vm.id for vm in self.schedule.vms])
            if self.faults.drop_frame(frame.seq):
                self._run_counters["frames_shed"] = \
                    self._run_counters.get("frames_shed", 0) + 1
                _FRAMES_SHED.inc()
                return "shed", None
        deadline_at = (now + self.robust.frame_deadline_intervals * interval
                       if interval > 0 else float("inf"))
        self._run_counters.pop("frame_lost_tuples", None)
        wait = 0.0
        try:
            try:
                outputs = self._launch_tasks(frame, deadline_at)
            except _FrameTimeout:
                self._run_counters["frames_timed_out"] = \
                    self._run_counters.get("frames_timed_out", 0) + 1
                _FRAMES_TIMED_OUT.inc()
                return "timeout", None
            # the frame's one block per sink: a truthful completion time,
            # and where an error of any part's device work surfaces
            self.last_sink_outputs = {}
            for snk in self.dag.sinks():
                out = outputs.get(snk.name)
                if out:
                    with _obs_span("executor.sink_wait"):
                        t0 = self.clock.now()
                        jax.block_until_ready(out)
                        wait += self.clock.now() - t0
                    _HOST_WAITS.inc()
                    self.last_sink_outputs[snk.name] = out
            if self._run_counters.pop("frame_lost_tuples", None):
                self._run_counters["frames_failed"] = \
                    self._run_counters.get("frames_failed", 0) + 1
                _FRAMES_FAILED.inc()
                return "failed", None
            return "ok", self.clock.now() - frame.created
        finally:
            # the waits on the executor's clock: none under a virtual one
            self._record_busy(wait)

    def _launch_tasks(self, frame: MicroBatch, deadline_at: float
                      ) -> Dict[str, Dict[str, jax.Array]]:
        """Every task's output of the frame, launched in topological order
        without a wait."""
        outputs: Dict[str, Dict[str, jax.Array]] = {}
        for t in self.dag.topo_order():
            ins = self.dag.in_edges(t.name)
            if not ins:
                arrays = frame.arrays
            else:
                upstream = [(i, outputs[e.src]) for i, e in enumerate(ins)
                            if e.src in outputs and outputs[e.src]]
                if not upstream:
                    continue
                if t.kind in MERGES:
                    arrays = self._merge(upstream)
                else:
                    # interleave: take one copy (sel 1:1)
                    arrays = upstream[0][1]
            outputs[t.name] = self._run_task(t.name, arrays, frame.seq,
                                             deadline_at)
        return outputs

    def run(self, omega: float, *, duration: float = 2.0,
            batch: int = 32, warmup_frames: int = 2,
            n_frames: Optional[int] = None, seed: int = 0) -> ExecutionReport:
        with _obs_span("executor.run", dag=self.schedule.dag.name,
                       omega=float(omega)):
            return self._run(omega, duration=duration, batch=batch,
                             warmup_frames=warmup_frames,
                             n_frames=n_frames, seed=seed)

    def _run(self, omega: float, *, duration: float = 2.0,
             batch: int = 32, warmup_frames: int = 2,
             n_frames: Optional[int] = None, seed: int = 0) -> ExecutionReport:
        source = SyntheticSource(omega, batch=batch, seed=seed,
                                 clock=self.clock,
                                 start_seq=self.frames_seen)
        interval = batch / omega if omega > 0 else 0.0
        latencies: List[float] = []
        tuples = 0
        counters = self._run_counters = {}
        escalated_before = list(self._pending_escalations)
        t0 = self.clock.now()
        frames = 0
        for frame in source.frames(duration, n_frames=n_frames):
            status, latency = self.process_frame(frame, interval)
            frames += 1
            if status == "ok":
                tuples += frame.size
                if frames > warmup_frames:
                    latencies.append(latency)
        self.frames_seen += frames
        wall = self.clock.now() - t0
        slope = latency_slope(latencies)
        mean_lat = float(np.mean(latencies)) if latencies else 0.0
        p99 = float(np.percentile(latencies, 99)) if latencies else 0.0
        # Stability: a genuinely overloaded executor falls behind its source
        # by ~(service - interval) per frame, i.e. the latency slope is on
        # the order of the frame interval.  Wall-clock jitter on the few
        # measured frames is far smaller, so judge the slope against a
        # fraction of the interval rather than an absolute constant.
        stable = slope <= max(1e-3, 0.05 * interval)
        reason = "" if stable else (
            f"latency slope {slope:.4g} s/frame exceeds the stability "
            f"bound for interval {interval:.4g} s")
        if not latencies:
            # degenerate window: zero post-warmup samples means nothing was
            # measured — report explicitly instead of vacuously passing
            stable = False
            reason = (f"no post-warmup latency samples (frames={frames}, "
                      f"warmup={warmup_frames}, "
                      f"shed={counters.get('frames_shed', 0)}, "
                      f"timed_out={counters.get('frames_timed_out', 0)}, "
                      f"failed={counters.get('frames_failed', 0)})")
        new_escalations = [v for v in self._pending_escalations
                           if v not in escalated_before]
        return ExecutionReport(
            omega=omega, frames=frames, tuples=tuples, wall_seconds=wall,
            throughput=tuples / wall if wall > 0 else 0.0,
            mean_latency=mean_lat, p99_latency=p99, latency_slope=slope,
            stable=stable, stable_reason=reason,
            device_frame_counts=dict(self._frame_count),
            frames_shed=counters.get("frames_shed", 0),
            frames_timed_out=counters.get("frames_timed_out", 0),
            frames_failed=counters.get("frames_failed", 0),
            retries=counters.get("retries", 0),
            tuples_lost=counters.get("tuples_lost", 0),
            escalated_vms=tuple(new_escalations),
        )

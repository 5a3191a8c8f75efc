"""Plain numpy reference of the fluid co-simulation (Sections 5-6 and 8.4 of
arXiv:1702.01785, as this system models them).

Independent of the program: it reads the DAG, the routing and the task
profiles from the configuration file, and takes from the program only the
answer under test, each schedule's thread counts per (task, slot) and its
VMs' speeds.  It builds its own group tables and runs its own tick loop.

Model: every (task, slot) group of ``q`` threads serves at the profile's
``I(q)`` (linear between measured thread counts, times the VM's speed),
throttled by the CPU-oversubscription penalty (a slot whose groups of one
DAG would draw more than one core serves at ``1 / over-use``, found by a
damped fixed point).  Shuffle routing sends each group a share
proportional to its threads.  Each tick of ``dt`` seconds moves arrivals
into the groups' queues in topological order and serves up to capacity; a
task's realized output feeds its successors in the same tick.  Latency
is, per DAG, the longest source-to-sink path of routing-weighted
``(queue + 1) / capacity`` plus flow-weighted hop latencies; a rate is
stable when the least-squares slope of the post-warm-up latency samples
is at most ``STABLE_SLOPE_PER_S``.
All DAGs of a fleet share the slots they map to, so busy time adds up.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

Slot = Tuple[int, int]

HOP_SAME_SLOT = 0.0002
HOP_SAME_VM = 0.001
HOP_CROSS_VM = 0.005
STABLE_SLOPE_PER_S = 1e-3
PENALTY_ITERATIONS = 8


@dataclasses.dataclass
class DagFacts:
    """One scheduled DAG as the reference sees it."""

    name: str
    tasks: Dict[str, str]                  # name -> kind, configuration order
    edges: List[Tuple[str, str, float]]    # (src, dst, selectivity)
    split: Dict[str, bool]                 # outgoing rate split over edges
    groups: Dict[str, Dict[Slot, int]]     # task -> slot -> threads
    vm_speed: Dict[int, float]
    omegas: np.ndarray                     # (K,) swept input rates


@dataclasses.dataclass
class DagResult:
    latency_samples: np.ndarray   # (n, K) post-warm-up latency samples
    latency_slope: np.ndarray     # (K,)
    stable: np.ndarray            # (K,)
    queue_total: np.ndarray       # (K,)
    slot_busy: Dict[Slot, np.ndarray]


def _interp(points: Sequence[Sequence[float]], field: int, q: float) -> float:
    taus = [0.0] + [float(p[0]) for p in points]
    vals = [0.0] + [float(p[field]) for p in points]
    return float(np.interp(float(q), taus, vals))


def _topo(tasks: Sequence[str], edges) -> List[str]:
    indeg = {t: 0 for t in tasks}
    for e in edges:
        indeg[e[1]] += 1
    ready, order = deque(t for t in tasks if indeg[t] == 0), []
    while ready:
        t = ready.popleft()
        order.append(t)
        for e in edges:
            if e[0] == t:
                indeg[e[1]] -= 1
                if indeg[e[1]] == 0:
                    ready.append(e[1])
    return order


class _Fleet:
    """Group tables of every DAG, stacked: rows are tasks, groups are
    (task, slot) pairs, slots are shared across DAGs."""

    def __init__(self, dags: Sequence[DagFacts],
                 profiles: Mapping[str, Mapping], dtype):
        self.dtype = dtype
        self.rows: List[Tuple[int, int]] = []          # group span per row
        self.in_edges: List[List[Tuple[int, float]]] = []
        self.hops: List[List[float]] = []
        self.sink_rows: List[List[int]] = []
        self.group_spans: List[Tuple[int, int]] = []
        self.row_spans: List[Tuple[int, int]] = []
        self.dag_slots: List[List[int]] = []
        g_frac, g_slot, g_task, g_cap, g_cpu, src = [], [], [], [], [], []
        self.slots: List[Slot] = []
        slot_row: Dict[Slot, int] = {}
        for d in dags:
            order = _topo(list(d.tasks), d.edges)
            row_of = {t: len(self.rows) + i for i, t in enumerate(order)}
            outs = {t: sum(1 for e in d.edges if e[0] == t) for t in order}
            beta: Dict[str, float] = {}
            g0, r0 = len(g_frac), len(self.rows)
            dag_slots = []
            for t in order:
                ins = [e for e in d.edges if e[1] == t]
                if not ins:
                    beta[t] = 1.0
                else:
                    beta[t] = sum(
                        beta[s] * sel / (max(1, outs[s]) if d.split[s]
                                         else 1)
                        for s, _, sel in ins)
                self.in_edges.append([
                    (row_of[s], sel / outs[s] if d.split[s] and outs[s]
                     else sel) for s, _, sel in ins])
                src.append(beta[t])
                lo = len(g_frac)
                g = d.groups.get(t, {})
                total = float(sum(g.values()))
                prof = profiles[d.tasks[t]]["points"]
                for s in sorted(g):
                    q = g[s]
                    if s not in slot_row:
                        slot_row[s] = len(self.slots)
                        self.slots.append(s)
                    if slot_row[s] not in dag_slots:
                        dag_slots.append(slot_row[s])
                    g_frac.append(q / total)
                    g_slot.append(slot_row[s])
                    g_task.append(row_of[t])
                    g_cap.append(_interp(prof, 1, q)
                                 * d.vm_speed.get(s[0], 1.0))
                    g_cpu.append(_interp(prof, 2, q))
                self.rows.append((lo, len(g_frac)))
            self.group_spans.append((g0, len(g_frac)))
            self.row_spans.append((r0, len(self.rows)))
            self.dag_slots.append(dag_slots)
            self.sink_rows.append([row_of[t] for t in order if outs[t] == 0])
        self.g_frac = np.asarray(g_frac, dtype=np.float64)
        self.g_slot = np.asarray(g_slot, dtype=int)
        self.g_task = np.asarray(g_task, dtype=int)
        self.g_cap = np.asarray(g_cap, dtype=np.float64)
        self.g_cpu = np.asarray(g_cpu, dtype=np.float64)
        self.beta = np.asarray(src, dtype=np.float64)
        # hop latency of each in-edge, weighted by the flow each (source
        # group, destination group) pair carries
        for r, edges in enumerate(self.in_edges):
            self.hops.append([self._hop(s, r) for s, _ in edges])

    def _hop(self, src_row: int, dst_row: int) -> float:
        (a0, a1), (b0, b1) = self.rows[src_row], self.rows[dst_row]
        if a0 == a1 or b0 == b1:
            return 0.0
        w = self.g_frac[a0:a1, None] * self.g_frac[None, b0:b1]
        sa, sb = self.g_slot[a0:a1], self.g_slot[b0:b1]
        vm_a = np.array([self.slots[s][0] for s in sa])
        vm_b = np.array([self.slots[s][0] for s in sb])
        hop = np.where(sa[:, None] == sb[None, :], HOP_SAME_SLOT,
                       np.where(vm_a[:, None] == vm_b[None, :],
                                HOP_SAME_VM, HOP_CROSS_VM))
        if w.sum() <= 0:
            return float(hop.mean())
        return float((w * hop).sum() / w.sum())

    def capacities(self, rates: np.ndarray) -> np.ndarray:
        """(G, K) capacity of every group at every swept rate, with the
        CPU-oversubscription penalty taken over each DAG's own groups.
        ``rates`` is (rows, K)."""
        caps = np.repeat(self.g_cap[:, None], rates.shape[1], axis=1)
        for g0, g1 in self.group_spans:
            base = self.g_cap[g0:g1, None]
            slot = self.g_slot[g0:g1]
            arrive = self.g_frac[g0:g1, None] * rates[self.g_task[g0:g1]]
            c = caps[g0:g1]
            for _ in range(PENALTY_ITERATIONS):
                used = self.g_cpu[g0:g1, None] * np.where(
                    base > 0, np.minimum(1.0, np.minimum(arrive, c)
                                         / np.where(base > 0, base, 1.0)),
                    1.0)
                slot_cpu = np.zeros((len(self.slots), rates.shape[1]))
                np.add.at(slot_cpu, slot, used)
                over = slot_cpu[slot]
                c = 0.5 * (c + np.where(over > 1.0 + 1e-9, base / over,
                                        base))
            caps[g0:g1] = c
        return caps


def simulate(dags: Sequence[DagFacts], profiles: Mapping[str, Mapping], *,
             duration: float, dt: float, warmup: float,
             sample_every: float, dtype=np.float64) -> List[DagResult]:
    """Co-simulate every DAG's rate sweep through one tick loop."""
    fl = _Fleet(dags, profiles, dtype)
    K = len(dags[0].omegas)
    omega = np.zeros((len(fl.rows), K))
    for d, (r0, r1) in zip(dags, fl.row_spans):
        omega[r0:r1] = np.asarray(d.omegas, dtype=np.float64)[None, :]
    src_rate = (fl.beta[:, None] * omega).astype(dtype)
    caps = fl.capacities(fl.beta[:, None] * omega).astype(dtype)
    steps = int(duration / dt)
    every = max(1, int(sample_every / dt))
    s0 = int(np.ceil(warmup / dt - 1e-9))
    if s0 >= steps or s0 < 0:
        s0 = 0
    dt_ = dtype(dt)
    G, S, T = len(fl.g_frac), len(fl.slots), len(fl.rows)
    frac = fl.g_frac.astype(dtype)[:, None]
    pos = caps > 0
    safe = np.where(pos, caps, dtype(1.0))
    queues = np.zeros((G, K), dtype)
    served = np.zeros((G, K), dtype)
    busy = np.zeros((S, K), dtype)
    realized = np.zeros((T, K), dtype)
    samples = []
    for step in range(steps):
        for r, (lo, hi) in enumerate(fl.rows):
            edges = fl.in_edges[r]
            rate = src_rate[r] if not edges else sum(
                (realized[s] * dtype(m) for s, m in edges),
                np.zeros(K, dtype))
            if lo == hi:
                realized[r] = rate
                continue
            q = queues[lo:hi] + rate[None, :] * frac[lo:hi] * dt_
            served[lo:hi] = np.minimum(q, caps[lo:hi] * dt_)
            queues[lo:hi] = q - served[lo:hi]
            realized[r] = served[lo:hi].sum(axis=0) / dt_
        if step >= s0:
            np.add.at(busy, fl.g_slot, np.where(pos, served / safe, 0))
        if step % every == 0:
            samples.append(_latency(fl, queues, caps, pos, safe, frac,
                                    dtype))
    lat = np.stack(samples)                       # (n, dags, K)
    times = np.arange(0, steps, every) * dt
    after = times >= s0 * dt - 1e-12
    k0 = int(np.argmax(after)) if after.any() else 0
    if len(times) - k0 < 3:
        k0 = 0
    interval = times[1] - times[0] if len(times) > 1 else 1.0
    window = max(steps - s0, 1) * dt
    out = []
    for i, (g0, g1) in enumerate(fl.group_spans):
        tail = lat[k0:, i, :].astype(np.float64)
        slope = _slope(tail) / interval
        out.append(DagResult(
            latency_samples=tail, latency_slope=slope,
            stable=slope <= STABLE_SLOPE_PER_S,
            queue_total=queues[g0:g1].astype(np.float64).sum(axis=0),
            slot_busy={fl.slots[s]: busy[s].astype(np.float64) / window
                       for s in fl.dag_slots[i]}))
    return out


def _latency(fl: _Fleet, queues, caps, pos, safe, frac, dtype) -> np.ndarray:
    K = queues.shape[1]
    contrib = np.where(pos, frac * (queues + dtype(1.0)) / safe, 0)
    per_task = np.zeros((len(fl.rows), K), dtype)
    np.add.at(per_task, fl.g_task, contrib)
    best = np.zeros_like(per_task)
    for r, edges in enumerate(fl.in_edges):
        if not edges:
            best[r] = per_task[r]
            continue
        up = np.full(K, -np.inf, dtype)
        for (s, _), hop in zip(edges, fl.hops[r]):
            up = np.maximum(up, best[s] + dtype(hop))
        best[r] = per_task[r] + up
    return np.stack([best[rows].max(axis=0) if rows else np.zeros(K, dtype)
                     for rows in fl.sink_rows])


def _slope(samples: np.ndarray) -> np.ndarray:
    n = samples.shape[0]
    if n < 2:
        return np.zeros(samples.shape[1])
    x = np.arange(n) - (n - 1) / 2.0
    return x @ (samples - samples.mean(axis=0)) / float((x ** 2).sum())

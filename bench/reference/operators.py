"""Plain numpy reference of a frame's path through a DAG's operators.

Independent of the program: it reads the DAG and the task kinds from the
configuration file and takes from the program only the answer under test,
the schedule's thread counts per (task, slot), which decide how a frame is
cut into parts.  The cut matters: ``batch_file_write``'s digest is a
cumulative sum within each part, and the external-service key is a sum
over each part.

Semantics (Storm's execution model over micro-batches, Section 2 of the
paper): tasks run in topological order; a task reads the output of its
first in-edge whose source produced one; a task's frame is cut over its
slot groups in (vm, slot) order, thread-proportionally, each part runs the
operator alone, and the parts are concatenated in the same order.
``dtype`` is the float type of the operator arithmetic: float32 as the
configuration states, or a lower one for the control.
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

Batch = Dict[str, np.ndarray]
Slot = Tuple[int, int]

MOD_DIGEST = 65521.0
PI_ITERATIONS = 15
SERVICE_WORK = 64


def topo_order(tasks: Sequence[str], edges: Sequence[Tuple[str, str]]
               ) -> List[str]:
    indeg = {t: 0 for t in tasks}
    for _, dst in edges:
        indeg[dst] += 1
    ready = deque(t for t in tasks if indeg[t] == 0)
    order = []
    while ready:
        t = ready.popleft()
        order.append(t)
        for src, dst in edges:
            if src == t:
                indeg[dst] -= 1
                if indeg[dst] == 0:
                    ready.append(dst)
    return order


def parse_xml(b: Batch, dtype) -> Batch:
    payload = b["payload"]
    nxt = np.roll(payload, -1, axis=-1)
    open_tag = (payload == ord("<")) & (nxt != ord("/"))
    return {**b, "tags": open_tag.sum(axis=-1, dtype=np.int32),
            "checksum": payload.astype(np.uint32).sum(axis=-1,
                                                      dtype=np.uint32)}


def pi(b: Batch, dtype) -> Batch:
    n = b["value"].shape[0]
    a = np.full(n, np.sqrt(np.float32(2.0)), dtype=dtype)
    two = dtype(2.0)
    prod = a / two
    for _ in range(PI_ITERATIONS - 1):
        a = np.sqrt(two + a).astype(dtype)
        prod = (prod * (a / two)).astype(dtype)
    return {**b, "pi": (two / prod).astype(np.float32)}


def batch_file_write(b: Batch, dtype) -> Batch:
    v = b["checksum"] if "checksum" in b else b["value"]
    digest = np.cumsum(v.astype(dtype), dtype=dtype) % dtype(MOD_DIGEST)
    return {**b, "digest": digest.astype(np.float32)}


def external_service(b: Batch, dtype) -> Batch:
    v = b["value"].astype(dtype)
    x = np.sum(v, dtype=dtype)
    for _ in range(SERVICE_WORK):
        x = dtype((x * dtype(1.000001) + dtype(0.5)) % dtype(1000.0))
    return {**b, "service": np.full(v.shape, x, dtype=np.float32)}


OPS = {"parse_xml": parse_xml, "pi": pi,
       "batch_file_write": batch_file_write,
       "azure_blob": external_service, "azure_table": external_service,
       "source": lambda b, dtype: b, "sink": lambda b, dtype: b}


def cut(groups: Mapping[Slot, int], n: int) -> List[Tuple[Slot, int, int]]:
    """``(slot, lo, hi)`` of each part of an ``n``-tuple frame: slots in
    (vm, slot) order, each taking a share proportional to its threads."""
    total = float(sum(groups.values()))
    slots = sorted(groups)
    bounds, acc = [], 0.0
    for s in slots[:-1]:
        acc += groups[s] / total
        bounds.append(int(round(acc * n)))
    bounds.append(n)
    out, lo = [], 0
    for s, hi in zip(slots, bounds):
        if hi > lo:
            out.append((s, lo, hi))
        lo = hi
    return out


def run_frame(tasks: Mapping[str, str], edges: Sequence[Tuple[str, str]],
              groups: Mapping[str, Mapping[Slot, int]], frame: Batch,
              dtype=np.float32) -> Dict[str, Batch]:
    """Sink name -> output arrays of one frame.

    ``tasks`` maps each task to its kind, ``edges`` lists ``(src, dst)`` in
    the configuration's order, ``groups`` gives each task's thread count per
    slot."""
    names = list(tasks)
    outputs: Dict[str, Batch] = {}
    for t in topo_order(names, edges):
        ins = [src for src, dst in edges if dst == t]
        if not ins:
            arrays = frame
        else:
            up = [outputs[s] for s in ins if outputs.get(s)]
            if not up:
                continue
            arrays = up[0]
        g = groups.get(t) or {}
        if not g:
            outputs[t] = arrays
            continue
        n = next(iter(arrays.values())).shape[0]
        parts = [OPS[tasks[t]]({k: v[lo:hi] for k, v in arrays.items()},
                               dtype)
                 for _, lo, hi in cut(g, n)]
        outputs[t] = {k: np.concatenate([p[k] for p in parts], axis=0)
                      for k in parts[0]} if parts else {}
    has_out = {src for src, _ in edges}
    return {t: outputs[t] for t in names
            if t not in has_out and outputs.get(t)}


def compare(got: Mapping[str, Mapping[str, np.ndarray]],
            want: Mapping[str, Mapping[str, np.ndarray]]
            ) -> Tuple[int, float]:
    """``(mismatches, float_err)`` of one frame's sink outputs.

    A mismatch is a missing or extra sink or field, a shape that differs,
    or an integer or byte element that differs.  ``float_err`` is the
    widest ``|got - want| / (1 + |want|)`` over the float fields."""
    bad, err = 0, 0.0
    for sink in set(got) | set(want):
        g, w = got.get(sink), want.get(sink)
        if g is None or w is None:
            bad += 1
            continue
        for key in set(g) | set(w):
            if key not in g or key not in w:
                bad += 1
                continue
            a, b = np.asarray(g[key]), np.asarray(w[key])
            if a.shape != b.shape:
                bad += 1
                continue
            if np.issubdtype(b.dtype, np.floating):
                a64, b64 = a.astype(np.float64), b.astype(np.float64)
                if a.size:
                    err = max(err, float(np.max(np.abs(a64 - b64)
                                                / (1.0 + np.abs(b64)))))
            else:
                bad += int(np.count_nonzero(a.astype(np.int64)
                                            != b.astype(np.int64)))
    return bad, err

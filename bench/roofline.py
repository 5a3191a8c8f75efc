"""The least time of a co-simulation call: its operations over the chip's
peak rate and its bytes over the chip's peak bandwidth, whichever is
larger.  Counted from the tick loop of ``reference/ticks.py`` at float64,
so it reads the same work whatever implements it."""

from __future__ import annotations

import json
import pathlib
from typing import Dict, Mapping, Sequence

from reference import ticks

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
F64 = 8


def peaks(device_kind: str) -> Mapping[str, float]:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table["devices"][device_kind]


def scan_work(dags: Sequence[ticks.DagFacts], profiles, *, duration: float,
              dt: float, sample_every: float) -> Dict[str, float]:
    """Operations and bytes of one co-simulation of ``dags``.

    Per tick and rate: each in-edge adds its source's rate (2 ops); each
    group takes its arrivals, queues, serves, dequeues and adds to its
    task's output (6 ops) and its slot's busy time (3 ops).  Per latency
    sample: each group's wait (4 ops), each in-edge's hop and maximum
    (2 ops), each task's sum (1 op).  Bytes: the inputs read once and the
    outputs written once, at 8 bytes a number; the loop's state is not
    counted, since a kernel may keep it on chip."""
    fl = ticks._Fleet(dags, profiles, None)
    G, T, S = len(fl.g_frac), len(fl.rows), len(fl.slots)
    E = sum(len(e) for e in fl.in_edges)
    K = len(dags[0].omegas)
    steps = int(duration / dt)
    every = max(1, int(sample_every / dt))
    samples = -(-steps // every)
    ops = steps * K * (9 * G + 2 * E) + samples * K * (4 * G + 2 * E + T)
    inputs = G * K + 2 * G + T * K + E
    outputs = 2 * G * K + S * K + T * K + samples * len(dags) * K
    return {"ops": float(ops), "bytes": float(F64 * (inputs + outputs))}


def least_time(work: Mapping[str, float], peak: Mapping[str, float]
               ) -> Dict[str, float]:
    t_ops = work["ops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes"}

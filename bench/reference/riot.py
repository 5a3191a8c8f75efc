"""Plain numpy reference of RIoTBench STATS over the SYS stream.

Independent of the program: it reads the DAG, the task kinds and the
kinds' parameters (``"parameters"``) from the configuration file, and keeps
one sequential state per (sensor, field) for the whole run.  Every frame of
a run goes through it in order, warm-up frames included.  A keyed kind's
answer does not depend on which thread owns a key, so the reference needs
nothing of the schedule for it; only the stateless kinds take the
schedule's thread counts per slot, which decide how ``azure_blob`` cuts a
frame into parts (as in ``operators.py``).

Semantics: tasks run in topological order; ``accumulate`` reads the union
of its in-edges' outputs (the rows of each in-edge in the configuration's
edge order, each field of any of them present with zeros where an in-edge
lacks it, ``branch`` the in-edge's index); every other task reads its first
in-edge that produced an output.  Every row carries ``valid``; a keyed kind
reads and writes its key's state only for a valid row, and a row it does
not emit is zero in every field it adds.  ``dtype`` is the float type of
the arithmetic: float32 as the configuration states, or a lower one for
the control.

It also counts the least work of the stateful kinds (:func:`state_work`)
for their roofline share.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

from reference import operators as ref_ops

Batch = Dict[str, np.ndarray]
F32 = 4
KEYED = ("average", "kalman_filter", "sliding_linear_regression",
         "distinct_approx_count", "accumulate")


def hash32(x) -> np.ndarray:
    """Murmur3's 32-bit finalizer: the hash of the routing and of the
    distinct count's buckets."""
    x = np.atleast_1d(np.asarray(x)).astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def senml_parse(b: Batch, fields: int, sensors: int) -> Batch:
    """Sensor id and timestamp as little-endian uint32, then ``fields``
    observations as little-endian float32; the rest of a record is
    padding.  A record whose sensor id is not below ``sensors`` is not
    valid."""
    raw = np.ascontiguousarray(b["payload"])
    sensor = raw[:, 0:4].copy().view("<u4")[:, 0]
    return {"sensor": sensor.astype(np.int32),
            "ts": raw[:, 4:8].copy().view("<u4")[:, 0].astype(np.int32),
            "obs": raw[:, 8:8 + 4 * fields].copy().view("<f4")
            .astype(np.float32),
            "valid": sensor < sensors}


class Stats:
    """The keyed kinds' state over a whole run, per task."""

    def __init__(self, cfg: Mapping, dtype=np.float32):
        self.p = cfg["parameters"]
        self.dtype = dtype
        spec = cfg["dags"][cfg["dag"]]
        self.tasks = {t[0]: t[1] for t in spec["tasks"]}
        self.edges = [(e[0], e[1]) for e in spec["edges"]]
        self.order = ref_ops.topo_order(list(self.tasks), self.edges)
        self.state: Dict[str, Dict] = {t: {} for t in self.tasks}
        self.valid_in: Dict[str, int] = {}

    def f(self, x):
        return np.asarray(x, dtype=self.dtype)

    # -- the keyed kinds, one row at a time ----------------------------------
    def average(self, st, row):
        s, n = st.get(row["sensor"], (self.f(np.zeros(self.p["fields"])), 0))
        s = self.f(s + self.f(row["obs"]))
        n += 1
        if n == self.p["w_avg"]:
            st[row["sensor"]] = (self.f(np.zeros_like(s)), 0)
            return {"avg": self.f(s / self.f(self.p["w_avg"]))}
        st[row["sensor"]] = (s, n)
        return None

    def kalman_filter(self, st, row):
        fields = self.p["fields"]
        x, p = st.get(row["sensor"], (self.f(np.zeros(fields)),
                                      self.f(np.full(fields,
                                                     self.p["kalman_p0"]))))
        p = self.f(p + self.f(self.p["kalman_q"]))
        k = self.f(p / self.f(p + self.f(self.p["kalman_r"])))
        x = self.f(x + self.f(k * self.f(self.f(row["obs"]) - x)))
        p = self.f(self.f(self.f(1.0) - k) * p)
        st[row["sensor"]] = (x, p)
        return {"kalman": x}

    def sliding_linear_regression(self, st, row):
        w, fields = self.p["w_slr"], self.p["fields"]
        ts, xs, n = st.get(row["sensor"], (np.zeros(w, np.int64),
                                           self.f(np.zeros((w, fields))), 0))
        ts, xs = ts.copy(), xs.copy()
        ts[n % w] = row["ts"]
        xs[n % w] = self.f(row["kalman"])
        n += 1
        st[row["sensor"]] = (ts, xs, n)
        m = min(n, w)
        held = np.arange(w) < m
        dt = self.f(np.where(held, (ts - row["ts"]).astype(np.float32), 0))
        mf = self.f(m)
        tm = self.f(np.sum(dt, dtype=self.dtype) / mf)
        xm = self.f(np.sum(np.where(held[:, None], xs, self.f(0)), axis=0,
                           dtype=self.dtype) / mf)
        dc = self.f(np.where(held, self.f(dt - tm), self.f(0)))
        sxx = self.f(np.sum(self.f(dc * dc), dtype=self.dtype))
        dx = self.f(np.where(held[:, None], self.f(xs - xm), self.f(0)))
        sxy = self.f(np.sum(self.f(dc[:, None] * dx), axis=0,
                            dtype=self.dtype))
        if m >= 2:
            slope = self.f(sxy / sxx)
            ahead = self.f(-np.min(dt) / self.f(mf - self.f(1)))
        else:
            slope, ahead = self.f(np.zeros(fields)), self.f(0)
        return {"slr": self.f(xm + self.f(slope * self.f(ahead - tm)))}

    def distinct_approx_count(self, st, row):
        bits = self.p["loglog_bits"]
        m = 1 << bits
        buckets = st.setdefault("buckets", np.zeros(m, np.int64))
        h = int(hash32(row["sensor"])[0])
        rest = h >> bits
        rank = 32 - rest.bit_length() - bits + 1
        b = h & (m - 1)
        buckets[b] = max(buckets[b], rank)
        mean = self.f(self.f(buckets.sum()) / self.f(m))
        return {"distinct": self.f(self.f(self.p["loglog_alpha"] * m)
                                   * self.f(np.exp2(mean)))}

    def accumulate(self, st, row):
        w = self.p["w_plot"]
        key = (row["sensor"], int(row["branch"]))
        ring, n = st.get(key, (self.f(np.zeros((w, 2 * self.p["fields"]
                                                 + 1))), 0))
        value = self.f(np.concatenate([row["avg"], row["slr"],
                                       [row["distinct"]]]))
        ring = ring.copy()
        ring[n % w] = value
        n += 1
        st[key] = (ring, n)
        return {"value": value, "window": ring, "filled": np.int32(n)}

    def _keyed(self, task: str, b: Batch) -> Batch:
        kind = self.tasks[task]
        step = getattr(self, kind)
        st = self.state[task]
        rows = b["valid"].shape[0]
        self.valid_in[task] = int(np.count_nonzero(b["valid"]))
        outs: List = []
        for i in range(rows):
            row = {k: v[i] for k, v in b.items()}
            row["sensor"] = int(row["sensor"])
            outs.append(step(st, row) if b["valid"][i] else None)
        out = {k: np.stack([np.asarray(o[k], dtype) if o is not None
                            else np.zeros(shape, dtype) for o in outs])
               for k, (shape, dtype) in self.emits(kind).items()}
        return {**b, **out, "valid": np.array([o is not None for o in outs])}

    def emits(self, kind: str) -> Dict[str, Tuple[tuple, type]]:
        """The fields a keyed kind adds to a row: shape and type."""
        f, s = self.p["fields"], 2 * self.p["fields"] + 1
        return {"average": {"avg": ((f,), np.float32)},
                "kalman_filter": {"kalman": ((f,), np.float32)},
                "sliding_linear_regression": {"slr": ((f,), np.float32)},
                "distinct_approx_count": {"distinct": ((), np.float32)},
                "accumulate": {"value": ((s,), np.float32),
                               "window": ((self.p["w_plot"], s), np.float32),
                               "filled": ((), np.int32)}}[kind]

    # -- one frame ------------------------------------------------------------
    def frame(self, payload: Batch, groups: Mapping[str, Mapping]
              ) -> Dict[str, Batch]:
        """Sink name -> output arrays of the next frame of the run."""
        outputs: Dict[str, Batch] = {}
        for t in self.order:
            ins = [s for s, d in self.edges if d == t]
            if not ins:
                arrays = payload
            else:
                up = [(i, outputs[s]) for i, s in enumerate(ins)
                      if outputs.get(s)]
                if not up:
                    continue
                arrays = union(up) if self.tasks[t] == "accumulate" \
                    else up[0][1]
            kind = self.tasks[t]
            if kind in ("source", "sink"):
                outputs[t] = arrays
            elif kind in KEYED:
                outputs[t] = self._keyed(t, arrays)
            else:
                op = (lambda b, _: senml_parse(b, self.p["fields"],
                                               self.p["sensors"])) \
                    if kind == "senml_parse" else ref_ops.OPS[kind]
                g = groups.get(t) or {(0, 0): 1}
                n = next(iter(arrays.values())).shape[0]
                parts = [op({k: v[lo:hi] for k, v in arrays.items()},
                            self.dtype) for _, lo, hi in ref_ops.cut(g, n)]
                outputs[t] = {k: np.concatenate([p[k] for p in parts])
                              for k in parts[0]}
        has_out = {s for s, _ in self.edges}
        return {t: outputs[t] for t in self.tasks
                if t not in has_out and outputs.get(t)}


def union(up: Sequence[Tuple[int, Batch]]) -> Batch:
    sizes = [next(iter(x.values())).shape[0] for _, x in up]
    like: Dict[str, np.ndarray] = {}
    for _, x in up:
        for k, v in x.items():
            like.setdefault(k, v)
    out = {k: np.concatenate([x[k] if k in x else
                              np.zeros((n,) + v.shape[1:], v.dtype)
                              for (_, x), n in zip(up, sizes)])
           for k, v in like.items()}
    out["valid"] = np.concatenate([x.get("valid", np.ones(n, bool))
                                   for (_, x), n in zip(up, sizes)])
    out["branch"] = np.concatenate([np.full(n, i, np.int32)
                                    for (i, _), n in zip(up, sizes)])
    return out


def state_work(p: Mapping, valid_in: Mapping[str, int],
               tasks: Mapping[str, str]) -> Dict[str, float]:
    """Least operations and bytes of one frame of the stateful kinds, from
    the valid rows each keyed task read (``valid_in``).

    Each valid row reads its key's state row and writes it back once, reads
    its input fields once and writes its output fields once, 4 bytes a
    number; a masked row costs nothing.  Operations per row: the average
    2 a field (add, and the mean's divide at most once a window, counted
    every row); the Kalman filter 8 a field; the regression over W pairs
    3W for the times' mean and centring and 5W + 3 a field (mean, centred
    products, slope, prediction); the distinct count 12 for the hash,
    bucket, rank and maximum and 3 for the estimate from a running sum
    of the ranks; the accumulator none (copies)."""
    f, w, wp = p["fields"], p["w_slr"], p["w_plot"]
    s = 2 * f + 1
    per_row = {
        # kind: (operations, state row numbers, input numbers, output numbers)
        "average": (2 * f, f + 1, 3 + f, f + 1),
        "kalman_filter": (8 * f, 2 * f, 3 + f, f + 1),
        "sliding_linear_regression": (3 * w + (5 * w + 3) * f,
                                      w * (1 + f) + 1, 3 + f, f + 1),
        "distinct_approx_count": (15, 1, 2, 2),
        "accumulate": (0, s + 1, 4 + s, s + wp * s + 2),
    }
    ops = nums = 0.0
    for task, rows in valid_in.items():
        o, st, i, out = per_row[tasks[task]]
        ops += rows * o
        nums += rows * (2 * st + i + out)
    return {"ops": ops, "bytes": F32 * nums}

"""Representative stream operators as JAX programs (Table 1 analogues).

Each operator consumes a micro-batch of tuples — a dict of arrays whose
leading axis is the tuple axis — and emits a micro-batch.  The JAX bodies are
jit-compiled once per (operator, batch shape) and run on the device backing
the resource slot the scheduler mapped the operator's threads to (the
executor places each input there before the call).

These mirror the profiler's single-tuple Python bodies (repro.core.profiler)
but vectorized: the executor processes tuples in micro-batches, which is also
how a TPU-resident DSPS would amortize dispatch.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Batch = Dict[str, jax.Array]


def _op_parse_xml(batch: Batch) -> Batch:
    """Byte-level tag scan over a (B, L) uint8 payload (SAX-like single
    pass): counts open tags and extracts a checksum feature per tuple."""
    payload = batch["payload"]  # (B, L) uint8
    lt = (payload == ord("<")).astype(jnp.int32)
    slash = (payload == ord("/")).astype(jnp.int32)
    nxt = jnp.roll(payload, -1, axis=-1)
    open_tag = lt * (1 - (nxt == ord("/")).astype(jnp.int32))
    tags = jnp.sum(open_tag, axis=-1)
    checksum = jnp.sum(payload.astype(jnp.uint32), axis=-1)
    return {**batch, "tags": tags, "checksum": checksum}


def _op_pi(batch: Batch, iterations: int = 15) -> Batch:
    """Viete's product, vectorized over tuples (FP-heavy)."""
    b = batch["value"].shape[0]
    a = jnp.full((b,), jnp.sqrt(2.0), dtype=jnp.float32)
    prod = a / 2.0

    def body(_, carry):
        a, prod = carry
        a = jnp.sqrt(2.0 + a)
        return a, prod * (a / 2.0)

    a, prod = jax.lax.fori_loop(0, iterations - 1, body, (a, prod))
    return {**batch, "pi": 2.0 / prod}


def _op_batch_file_write(batch: Batch, window: int = 64) -> Batch:
    """Windowed accumulation: running digest over the micro-batch (the host
    flush is performed by the executor when the digest window rolls)."""
    v = batch.get("checksum", batch.get("value", jnp.zeros(1))).astype(jnp.float32)
    digest = jnp.cumsum(v) % 65521.0  # adler-style rolling digest
    return {**batch, "digest": digest}


def _op_external_service(batch: Batch, work: int = 64) -> Batch:
    """Azure Blob/Table stand-in: light on-device work; the service latency
    is injected by the executor (host-side wait), matching the profiler's
    ExternalService model."""
    v = batch.get("value", jnp.zeros(batch["payload"].shape[0]
                                     if "payload" in batch else 1))
    key = jnp.sum(v.astype(jnp.float32))

    def body(_, x):
        return (x * 1.000001 + 0.5) % 1000.0

    looked_up = jax.lax.fori_loop(0, work, body, key)
    return {**batch, "service": jnp.broadcast_to(looked_up, v.shape)}


OPERATORS: Dict[str, Callable[[Batch], Batch]] = {
    "parse_xml": _op_parse_xml,
    "pi": _op_pi,
    "batch_file_write": _op_batch_file_write,
    "azure_blob": _op_external_service,
    "azure_table": _op_external_service,
    "source": lambda b: b,
    "sink": lambda b: b,
}

#: host-side service latency (s) injected per micro-batch for external tasks
SERVICE_LATENCY = {"azure_blob": 0.010, "azure_table": 0.005}


# ---------------------------------------------------------------------------
# RIoTBench STATS over the SYS stream (Shukla & Simmhan, arXiv:1606.07621):
# SenML parse, then per-sensor block average, Kalman filter -> sliding linear
# regression and a distinct approximate count, joined by an accumulator.
# The stateful kinds are ``fn(state, batch) -> (state, batch)``; every row
# carries a ``valid`` mask, and an output row that is not valid is zero, so
# a frame keeps its static shape whatever a kind emits.
# ---------------------------------------------------------------------------

#: sensors of the SYS stream held in a state table (dense ids 0..n-1)
SYS_SENSORS = 1000
#: observation fields of a SYS record: temperature, humidity, light, dust,
#: air quality
SYS_FIELDS = 5
#: bytes of one SenML record: sensor id and timestamp (ms) as little-endian
#: uint32, the observations as little-endian float32, then padding
RECORD_BYTES = 32
W_AVG = 10                   # block average window, per (sensor, field)
W_SLR = 10                   # regression window, per (sensor, field)
W_PLOT = 10                  # values kept per (sensor, branch) for the plot
KALMAN_Q, KALMAN_R, KALMAN_P0 = 0.125, 0.32, 30.0
LOGLOG_BITS = 10             # m = 2**LOGLOG_BITS buckets
LOGLOG_ALPHA = 0.39701       # Durand-Flajolet's alpha for large m
#: fields the accumulator keeps of each row, in this order: the average's,
#: the regression's and the distinct count's
ACC_SERIES = ("avg", "slr", "distinct")
ACC_BRANCHES = 3
ACC_WIDTH = 2 * SYS_FIELDS + 1
#: the parameters above under the names a deployment's configuration gives
#: them (its ``"parameters"``): the state tables are sized by them, so a
#: deployment that states others cannot run on these kinds
PARAMETERS = {"sensors": SYS_SENSORS, "fields": SYS_FIELDS,
              "record_bytes": RECORD_BYTES, "w_avg": W_AVG, "w_slr": W_SLR,
              "w_plot": W_PLOT, "kalman_q": KALMAN_Q, "kalman_r": KALMAN_R,
              "kalman_p0": KALMAN_P0, "loglog_bits": LOGLOG_BITS,
              "loglog_alpha": LOGLOG_ALPHA}

State = Dict[str, jax.Array]


def hash32(x, xp=jnp):
    """Murmur3's 32-bit finalizer of a uint32 array: the hash that routes a
    key to its thread and that the distinct count draws its buckets from
    (``xp`` is ``jax.numpy`` or ``numpy``)."""
    x = xp.asarray(x).astype(xp.uint32)
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> xp.uint32(13))
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> xp.uint32(16))


def _op_senml_parse(batch: Batch) -> Batch:
    """Decode each fixed-layout SenML record of a (B, RECORD_BYTES) uint8
    payload into its sensor id, timestamp and observations; a record whose
    sensor id has no state row is masked invalid."""
    raw = batch["payload"]
    words = jax.lax.bitcast_convert_type(
        raw[:, :8 + 4 * SYS_FIELDS].reshape(raw.shape[0], -1, 4), jnp.uint32)
    obs = jax.lax.bitcast_convert_type(words[:, 2:], jnp.float32)
    return {"sensor": words[:, 0].astype(jnp.int32),
            "ts": words[:, 1].astype(jnp.int32), "obs": obs,
            "valid": words[:, 0] < jnp.uint32(SYS_SENSORS)}


def _scan_rows(step, state: State, batch: Batch, key: Optional[str],
               fields: Tuple[str, ...]):
    """Run ``step(state_row, inputs) -> (state_row, out, emit)`` over the
    batch's rows in arrival order.  Row ``i`` reads and writes the state row
    of its key (row 0 without a key), and only while it is valid; it emits
    ``out`` where it is valid and ``emit`` holds, zeros elsewhere."""
    n = batch["valid"].shape[0]
    row_of = batch[key] if key else jnp.zeros((n,), jnp.int32)

    def body(st, xs):
        r, valid, inputs = xs
        old = {k: v[r] for k, v in st.items()}
        new, out, emit = step(old, inputs)
        st = {k: st[k].at[r].set(jnp.where(valid, new[k], old[k]))
              for k in st}
        ok = valid & emit
        return st, ({k: jnp.where(ok, v, jnp.zeros_like(v))
                     for k, v in out.items()}, ok)

    xs = (row_of, batch["valid"], {f: batch[f] for f in fields})
    state, (out, ok) = jax.lax.scan(body, state, xs)
    return state, {**batch, **out, "valid": ok}


def _op_average(state: State, batch: Batch):
    """Block average of W_AVG observations per (sensor, field): a row that
    closes its sensor's window emits the mean; other rows are masked."""
    def step(st, x):
        s = st["sum"] + x["obs"]
        n = st["n"] + 1
        closed = n == W_AVG
        return ({"sum": jnp.where(closed, 0.0, s), "n": jnp.where(closed, 0, n)},
                {"avg": s / jnp.float32(W_AVG)}, closed)
    return _scan_rows(step, state, batch, "sensor", ("obs",))


def _op_kalman_filter(state: State, batch: Batch):
    """Scalar Kalman filter per (sensor, field): ``p += q; k = p/(p+r);
    x += k(z - x); p = (1 - k)p``; emits the estimate ``x``."""
    def step(st, x):
        p = st["p"] + jnp.float32(KALMAN_Q)
        k = p / (p + jnp.float32(KALMAN_R))
        est = st["x"] + k * (x["obs"] - st["x"])
        return {"x": est, "p": (1.0 - k) * p}, {"kalman": est}, True
    return _scan_rows(step, state, batch, "sensor", ("obs",))


def _op_sliding_linear_regression(state: State, batch: Batch):
    """Least-squares line over the last W_SLR (t, x) pairs of each (sensor,
    field), t in ms relative to the newest; emits the line's value one mean
    interval after the newest timestamp (the value itself while the window
    holds one pair)."""
    def step(st, x):
        pos = st["n"] % W_SLR
        ts = st["ts"].at[pos].set(x["ts"])
        xs = st["x"].at[pos].set(x["kalman"])
        n = st["n"] + 1
        m = jnp.minimum(n, W_SLR)
        held = jnp.arange(W_SLR) < m
        dt = jnp.where(held, (ts - x["ts"]).astype(jnp.float32), 0.0)
        mf = m.astype(jnp.float32)
        tm = jnp.sum(dt) / mf
        xm = jnp.sum(jnp.where(held[:, None], xs, 0.0), axis=0) / mf
        dc = jnp.where(held, dt - tm, 0.0)
        sxx = jnp.sum(dc * dc)
        sxy = jnp.sum(dc[:, None] * jnp.where(held[:, None], xs - xm, 0.0),
                      axis=0)
        two = m >= 2
        slope = jnp.where(two, sxy / jnp.where(two, sxx, 1.0), 0.0)
        step_ahead = jnp.where(two, -jnp.min(dt) / jnp.maximum(mf - 1.0, 1.0),
                               0.0)
        return ({"ts": ts, "x": xs, "n": n},
                {"slr": xm + slope * (step_ahead - tm)}, True)
    return _scan_rows(step, state, batch, "sensor", ("ts", "kalman"))


def _loglog_rank(h):
    """Bucket and rank of a hash: bucket = h mod m; rank = 1 + the leading
    zeros of the remaining 32 - LOGLOG_BITS bits."""
    bucket = (h & jnp.uint32((1 << LOGLOG_BITS) - 1)).astype(jnp.int32)
    rest = h >> jnp.uint32(LOGLOG_BITS)
    rank = jax.lax.clz(rest).astype(jnp.int32) - LOGLOG_BITS + 1
    return bucket, rank


def _op_distinct_approx_count(state: State, batch: Batch):
    """Durand-Flajolet LogLog count of distinct sensor ids over the whole
    stream, one bucket array held by a single thread; each row emits the
    estimate ``alpha m 2**(mean rank)`` once it has been counted."""
    m = 1 << LOGLOG_BITS

    def step(st, x):
        bucket, rank = _loglog_rank(hash32(x["sensor"]))
        buckets = st["buckets"].at[bucket].max(rank)
        mean = jnp.sum(buckets).astype(jnp.float32) / jnp.float32(m)
        est = jnp.float32(LOGLOG_ALPHA * m) * jnp.exp2(mean)
        return {"buckets": buckets}, {"distinct": est}, True
    return _scan_rows(step, state, batch, None, ("sensor",))


def _op_accumulate(state: State, batch: Batch):
    """The plot's input: the last W_PLOT rows of each branch per sensor.  A
    row's ``value`` is its average, regression and distinct-count fields in
    a row (a branch leaves the others zero).  Each row emits the window of
    its (sensor, branch) after it, in ring order, and how many rows the
    window has taken."""
    def step(st, x):
        value = jnp.concatenate([jnp.atleast_1d(x[f]) for f in ACC_SERIES])
        n = st["n"][x["branch"]]
        ring = st["ring"].at[x["branch"], n % W_PLOT].set(value)
        n = n + 1
        return ({"ring": ring, "n": st["n"].at[x["branch"]].set(n)},
                {"value": value, "window": ring[x["branch"]], "filled": n},
                True)
    return _scan_rows(step, state, batch, "sensor", (*ACC_SERIES, "branch"))


def _zeros(*shape, dtype=jnp.float32):
    return lambda: jnp.zeros(shape, dtype)


@dataclasses.dataclass(frozen=True)
class Keyed:
    """A stateful kind: ``fn(state, batch) -> (state, batch)`` with its
    state table, one row per key (``rows``), made by ``init``.  ``key`` is
    the field that picks a tuple's thread (Storm's fields grouping); None
    sends every tuple to the task's first thread (global grouping)."""

    fn: Callable[[State, Batch], Tuple[State, Batch]]
    init: Callable[[], State]
    key: Optional[str] = "sensor"

    @property
    def rows(self) -> int:
        return next(iter(jax.eval_shape(self.init).values())).shape[0]


KEYED: Dict[str, Keyed] = {
    "average": Keyed(_op_average, lambda: {
        "sum": jnp.zeros((SYS_SENSORS, SYS_FIELDS), jnp.float32),
        "n": jnp.zeros((SYS_SENSORS,), jnp.int32)}),
    "kalman_filter": Keyed(_op_kalman_filter, lambda: {
        "x": jnp.zeros((SYS_SENSORS, SYS_FIELDS), jnp.float32),
        "p": jnp.full((SYS_SENSORS, SYS_FIELDS), KALMAN_P0, jnp.float32)}),
    "sliding_linear_regression": Keyed(
        _op_sliding_linear_regression, lambda: {
            "ts": jnp.zeros((SYS_SENSORS, W_SLR), jnp.int32),
            "x": jnp.zeros((SYS_SENSORS, W_SLR, SYS_FIELDS), jnp.float32),
            "n": jnp.zeros((SYS_SENSORS,), jnp.int32)}),
    "distinct_approx_count": Keyed(_op_distinct_approx_count, lambda: {
        "buckets": jnp.zeros((1, 1 << LOGLOG_BITS), jnp.int32)}, key=None),
    "accumulate": Keyed(_op_accumulate, lambda: {
        "ring": jnp.zeros((SYS_SENSORS, ACC_BRANCHES, W_PLOT, ACC_WIDTH),
                          jnp.float32),
        "n": jnp.zeros((SYS_SENSORS, ACC_BRANCHES), jnp.int32)}),
}

#: kinds whose input is the union of every in-edge's output; every other
#: kind reads its first in-edge that produced one
MERGES = frozenset({"accumulate"})

OPERATORS["senml_parse"] = _op_senml_parse

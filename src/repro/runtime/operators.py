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

from typing import Callable, Dict

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

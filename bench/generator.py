"""Open-loop load generation: due times and tuple payloads.

The schedule of a run is fixed by the cell and does not slow when the
system does: every frame has a due time computed before the window
starts, and latency is measured from that due time.  The seed makes only
the data (payload bytes and values, rate fractions, pool seeds); it never
changes how many items are due or when.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def frame_times(rate: float, frame_tuples: int, seconds: float) -> List[float]:
    """Due time, in seconds after the window start, of every frame of
    ``frame_tuples`` tuples offered at ``rate`` tuples/s in ``[0, seconds)``."""
    interval = frame_tuples / float(rate)
    n = int(np.ceil(seconds / interval - 1e-9))
    return [k * interval for k in range(n)]


def frame_payload(seed: int, seq: int, frame_tuples: int,
                  payload_bytes: int) -> Dict[str, np.ndarray]:
    """The tuples of frame ``seq``: printable bytes and a float32 value per
    tuple, from ``(seed, seq)`` alone, so any frame can be made again."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 int(seq)])
    payload = rng.integers(32, 127, size=(frame_tuples, payload_bytes),
                           dtype=np.uint8)
    value = rng.random(frame_tuples, dtype=np.float32)
    return {"payload": payload, "value": value}


def draws(seed: int, what: str) -> np.random.Generator:
    """A generator for the ``what`` stream of a seed (fractions, pool
    seeds, samples), independent of the other streams."""
    tag = int.from_bytes(what.encode()[:4].ljust(4, b"\0"), "little")
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  tag, 1 << 20])

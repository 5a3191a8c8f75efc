"""Clean hand-over-hand pattern: the analyzer must stay silent here.

The future is swapped out *under* the lock and blocked on with the lock
released — the shape an asynchronous writer's ``wait`` takes.
Zero findings expected (the false-positive guard for RACE211/RACE212).
"""

import concurrent.futures
import threading
from typing import Optional


class AsyncWriter:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self._pending: Optional[concurrent.futures.Future] = None

    def submit(self, fn) -> None:
        self.wait()
        with self._lock:
            self._pending = self._pool.submit(fn)

    def wait(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, None
        if pending is not None:
            pending.result()

import time

import jax
import numpy as np

from compiles import CompileLog


def test_compile_log_counts_compiles_inside_a_window_only():
    log = CompileLog()
    jax.jit(lambda x: x * 3 + 1)(np.ones(5)).block_until_ready()
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 5 - 2)(np.ones(7)).block_until_ready()
    t1 = time.perf_counter()
    assert len(log.times) >= 2
    assert log.within((t0, t1)) == 1

"""Host time per search request in the traced window: its wall time less
the vmapped programs' device time (candidate pool, group indexes,
padding, verdicts).  Read only from a trace that kept every launch."""

import stats

PROGRAM = "jit_batched_kernel"


def read(run):
    requests = run.items("bench.search")
    trace = run.device_trace
    if trace is None or not requests:
        return None
    device_s = trace.modules_s.get(PROGRAM, 0.0) / len(requests)
    return (stats.mean([i.end - i.start for i in requests]) - device_s) * 1e3

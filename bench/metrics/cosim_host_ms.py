"""Host time per co-simulation call in the traced window: the call's wall
time less the scan program's device time per call (host set-up, copies,
post-processing).  Read only from a trace that kept every launch."""

import stats

PROGRAM = "jit_kernel"


def read(run):
    calls = run.items("bench.cosim")
    trace = run.device_trace
    if trace is None or not calls:
        return None
    device_s = trace.modules_s.get(PROGRAM, 0.0) / len(calls)
    return (stats.mean([i.end - i.start for i in calls]) - device_s) * 1e3

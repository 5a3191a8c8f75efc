"""Device time per launch of the co-simulation's scan program, by the
name the program carries in the trace (``jit_kernel``)."""

PROGRAM = "jit_kernel"


def read(run):
    trace = run.device_trace
    if trace is None or not trace.modules_n.get(PROGRAM):
        return None
    return trace.modules_s[PROGRAM] * 1e3 / trace.modules_n[PROGRAM]

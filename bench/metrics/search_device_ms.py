"""Device time per search request in the traced window of the vmapped
candidate programs (``jit_batched_kernel``)."""

PROGRAM = "jit_batched_kernel"


def read(run):
    requests = run.items("bench.search")
    trace = run.device_trace
    if trace is None or not requests:
        return None
    return trace.modules_s.get(PROGRAM, 0.0) * 1e3 / len(requests)

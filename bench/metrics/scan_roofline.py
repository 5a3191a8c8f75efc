"""The scan program's least time (its operations over the peak, or its
bytes over the peak bandwidth, whichever is larger, counted at float64 by
``roofline.py``) as a share of its device time per launch."""

import roofline

PROGRAM = "jit_kernel"


def read(run):
    work = getattr(run.load, "scan_work", None)
    trace = run.device_trace
    if (trace is None or work is None or not run.peaks
            or not trace.modules_n.get(PROGRAM)):
        return None
    device_s = trace.modules_s[PROGRAM] / trace.modules_n[PROGRAM]
    return 100.0 * roofline.least_time(work, run.peaks)["seconds"] / device_s

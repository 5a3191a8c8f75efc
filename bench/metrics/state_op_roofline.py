"""The stateful kinds' least time over their programs' device time, in %,
over the frames of the traced window.  A frame's least time is its
operations over the peak or its bytes over the peak bandwidth, whichever
is larger, from the work ``reference/riot.py`` counts for the rows each
keyed task read (``state_work`` of the load), so it reads the same work
whatever implements it."""

import roofline


def read(run):
    programs = getattr(run.load, "state_programs", None)
    work = getattr(run.load, "state_work", None)
    frames = run.items("frame")
    trace = run.device_trace
    if trace is None or not programs or not work or not frames \
            or not run.peaks:
        return None
    device_s = sum(trace.modules_s.get(p, 0.0) for p in programs)
    if device_s <= 0:
        return None
    least = sum(roofline.least_time(work[i.seq], run.peaks)["seconds"]
                for i in frames)
    return 100.0 * least / device_s

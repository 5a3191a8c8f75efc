"""Device time per frame of the stateful kinds' programs, by the names the
load publishes (``state_programs``: ``jit__op_average`` and the rest),
over the frames of the traced window."""


def read(run):
    programs = getattr(run.load, "state_programs", None)
    frames = run.items("frame")
    trace = run.device_trace
    if trace is None or not programs or not frames \
            or not any(trace.modules_n.get(p) for p in programs):
        return None
    return sum(trace.modules_s.get(p, 0.0) for p in programs) * 1e3 \
        / len(frames)

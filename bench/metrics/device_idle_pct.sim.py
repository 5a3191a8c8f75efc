"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the window,
the mean over the cell's chips."""


def read(run):
    trace = run.device_trace
    return None if trace is None else trace.idle_pct

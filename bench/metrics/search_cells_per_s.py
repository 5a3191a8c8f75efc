"""Candidate x rate x tick cells the mapper searches simulated over the
whole window, divided by the window's length (from its start to the end of
its last request)."""


def read(run):
    items = run.items()
    if not items:
        return None
    t0, t1 = run.window
    return sum(i.cells for i in items) / (t1 - t0)

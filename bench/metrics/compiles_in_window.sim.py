"""XLA backend compiles whose end falls inside the window (a compile
served from the persistent cache counts too): zero when the warm-up
covered every shape."""


def read(run):
    return float(run.compiles.within(run.window))

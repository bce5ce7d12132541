"""Backend compiles JAX reported inside the window (a read from the
persistent compile cache counts too): 0 when the warm-up held every shape."""


def read(run):
    return run.window.compiles

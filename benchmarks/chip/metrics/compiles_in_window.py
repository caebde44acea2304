"""Executables JAX built (compiled, or loaded from the persistent cache)
inside the measured window, from its compile events.  Should read 0."""


def read(run):
    return run.compiles_in_window

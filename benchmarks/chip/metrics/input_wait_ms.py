"""Milliseconds a step waited for its batch: the benchmark's host span
around each ``next(loader)`` in the window, totalled and divided by the
window's steps."""


def read(run):
    return 1e3 * run.input_wait_s / run.steps

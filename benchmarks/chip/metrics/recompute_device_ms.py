"""Device self time a step (ms) of the backward pass's recomputation:
every op whose ``op_name`` holds the checkpoint's
``rematted_computation``, whatever its scope.  ``mfu`` does not count
this work."""

from benchmarks.chip import opscope


def read(run):
    return opscope.device_ms(run, phase="recompute")

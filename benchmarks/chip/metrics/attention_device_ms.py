"""Device self time a step (ms) of the ops under the program's
``attention`` scope (``norm1`` through the mixer's residual add):
forward, backward and the backward pass's recomputation together."""

from benchmarks.chip import opscope


def read(run):
    s = opscope.scopes
    return None if s is None else opscope.device_ms(run, under=(s.ATTENTION,))

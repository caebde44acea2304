"""Device self time a step (ms) of the ops under the program's
``optimizer`` scope: gradient clipping and the AdamW update."""

from benchmarks.chip import opscope


def read(run):
    s = opscope.scopes
    return None if s is None else opscope.device_ms(run, under=(s.OPTIMIZER,))

"""Device self time a step (ms) of the MoE layer's data movement: the
ops under ``ffn/router``, ``ffn/dispatch`` and ``ffn/combine``, the
kernels with the relayout copies beside them, in every pass."""

from benchmarks.chip import opscope


def read(run):
    s = opscope.scopes
    if s is None:
        return None
    return opscope.device_ms(run, under=tuple(
        f"{s.FFN}/{part}" for part in (s.ROUTER, s.DISPATCH, s.COMBINE)))

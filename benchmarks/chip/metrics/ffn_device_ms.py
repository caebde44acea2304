"""Device self time a step (ms) of the ops under the program's ``ffn``
scope (``norm2`` through the FFN's residual add): the dense SwiGLU, or
the whole MoE layer with its router, dispatch, experts and combine;
forward, backward and recomputation together."""

from benchmarks.chip import opscope


def read(run):
    s = opscope.scopes
    return None if s is None else opscope.device_ms(run, under=(s.FFN,))

"""Model FLOP utilization (%) of the whole train step: the forward and
backward FLOPs the model requires per token (``flops.py``: backward = 2 ×
forward, causal attention, top-k experts without capacity padding, no
recomputation) times tokens per second of the window, over chips × the
chip's bf16 peak (``peaks.json``)."""


def read(run):
    rate = run.tokens / run.window_s
    return 100.0 * rate * run.flops_per_token / (
        run.chips * run.peaks["bf16_flops"])

"""Share (%) of the MoE dispatch kernel's roofline: the least time its
calls in the traced window could take (bytes from each call's shapes
over the HBM peak; its arithmetic is far below the compute bound) over
their device time.  Nothing to read where the trace has no dispatch
kernel."""

from benchmarks.chip.flops import roofline_share


def read(run):
    if run.trace is None:
        return None
    share = roofline_share("dispatch", run.trace.kernel_ops("dispatch_pallas"),
                           run.peaks)
    return None if share is None else share[0]

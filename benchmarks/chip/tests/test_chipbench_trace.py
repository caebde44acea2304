"""The trace reduction: on hand-made intervals, and on a small trace
recorded on a TPU v5e (``testdata/trace_probe.xplane.pb.gz``: three
steps of a 1-layer MoE decoder, d_model 256, 8 experts top-2, batch
2 × 256 tokens, microbatch 1, through the program's train step with the
compiled Pallas dispatch/combine kernels, and the benchmark's host spans
``bench.window``, ``bench.next_batch``, ``bench.dispatch``,
``bench.sync``)."""

from __future__ import annotations

import dataclasses
from pathlib import Path

import pytest

from benchmarks.chip import flops, spec, trace

PROBE = Path(__file__).resolve().parents[1] / "testdata" / "trace_probe.xplane.pb.gz"


def test_union_and_gaps_by_hand():
    ops = [trace.Op("%a", 0, 10), trace.Op("%b", 5, 15),
           trace.Op("%c", 20, 30), trace.Op("%d", 40, 45)]
    assert trace._union(ops, 0, 25) == [(0, 15), (20, 25)]
    r = trace.Reduced(window=(0.0, 50.0), ops={"/device:TPU:0": ops},
                      spans=[("bench.window", 0, 50), ("bench.sync", 14, 22),
                             ("bench.dispatch", 30, 42)])
    assert r.busy_s() == pytest.approx(30e-9)
    assert r.idle_gaps() == [["bench.dispatch", pytest.approx(10e-9)],
                             ["bench.sync", pytest.approx(5e-9)],
                             ["none", pytest.approx(5e-9)]]
    assert r.top_ops(2) == [["a", pytest.approx(10e-9)],
                            ["b", pytest.approx(10e-9)]]
    nested = trace.Reduced(window=(0.0, 50.0), ops={"/device:TPU:0": [
        trace.Op("%loop", 0, 40), trace.Op("%body", 5, 30),
        trace.Op("%inner", 10, 20), trace.Op("%next", 32, 38)]},
        spans=[])
    assert dict(map(tuple, nested.top_ops())) == {
        "loop": pytest.approx(9e-9), "body": pytest.approx(15e-9),
        "inner": pytest.approx(10e-9), "next": pytest.approx(6e-9)}


def test_trace_without_window_is_refused():
    class P:
        planes = []

    with pytest.raises(ValueError):
        trace.reduce(P())


@pytest.fixture(scope="module")
def probe():
    return trace.reduce(trace.load(PROBE.read_bytes()))


def test_recorded_trace_busy_and_gaps(probe):
    assert list(probe.ops) == ["/device:TPU:0"]
    assert 0 < probe.busy_s() < probe.window_s
    names = {name for name, _ in probe.idle_gaps()}
    assert names <= {"bench.next_batch", "bench.dispatch", "bench.sync", "none"}
    assert len(probe.top_ops()) == 10


def test_recorded_trace_kernels(probe):
    # the device's clock runs about 1.6 ms behind the host's here, so the
    # first step's first kernels fall before the host's window opens;
    # count over the whole trace
    ops = probe.ops["/device:TPU:0"]
    whole = dataclasses.replace(probe, window=(min(o.start for o in ops),
                                               max(o.end for o in ops)))
    dispatch = whole.kernel_ops("dispatch_pallas")
    combine = whole.kernel_ops("combine_pallas")
    # 3 steps × 2 microbatches × (forward, its recomputation in the
    # backward pass, combine's backward) for dispatch; combine's own
    # recomputation is dead code (its backward needs only its inputs)
    assert len(dispatch) == 18 and len(combine) == 12
    peaks = spec.Benchmark().peaks("TPU v5 lite")
    for kind, ops in (("dispatch", dispatch), ("combine", combine)):
        share, bound = flops.roofline_share(kind, ops, peaks)
        assert 0 < share < 100 and bound == "memory"

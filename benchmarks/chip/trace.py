"""Device trace of a measured window, and its reduction to numbers.

``capture`` records the JAX profiler's trace of a block of code into a
temporary directory and returns it as ``ProfileData``.  The reduction
reads the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane and the
benchmark's host spans (``bench.*`` TraceAnnotations), which the
profiler puts on the same clock:

- busy time: the union of the device op intervals inside the window;
- kernel time: the summed durations of the ops whose HLO name starts
  with a kernel's name;
- idle gaps: the stretches inside the window where no op ran, each named
  by the host span open at its middle.

The two clocks are not exactly one: in a trace recorded on a TPU v5e
(``testdata/``) each program on the device ends about 1.6 ms, on the
host's clock, before the host sees it done.  A window of seconds is not
moved by that, but a gap of a few milliseconds may carry the name of the
neighbouring host span.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field

#: host span around the whole measured window
WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load(data: bytes):
    """ProfileData from the bytes of an ``.xplane.pb`` file, gzipped or
    not."""
    from jax.profiler import ProfileData

    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


@contextlib.contextmanager
def capture(box: list):
    """Trace the block; on exit append its ``ProfileData`` to ``box``.
    The trace's files go to a temporary directory that is removed."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="chip-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        with open(path, "rb") as f:
            box.append(load(f.read()))


@dataclass
class Op:
    name: str          # the HLO instruction's text
    start: float       # ns
    end: float         # ns

    @property
    def short(self) -> str:
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclass
class Reduced:
    window: tuple[float, float]                    # ns, from the host span
    ops: dict[str, list[Op]]                       # per device plane
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of op intervals inside the window, averaged over chips."""
        lo, hi = self.window
        per_chip = [sum(b - a for a, b in _union(ops, lo, hi))
                    for ops in self.ops.values()]
        return sum(per_chip) / len(per_chip) * 1e-9 if per_chip else 0.0

    def kernel_ops(self, prefix: str) -> list[Op]:
        """Ops inside the window whose HLO name starts with ``prefix``."""
        lo, hi = self.window
        return [o for ops in self.ops.values() for o in ops
                if o.short.startswith(prefix) and lo <= o.start and o.end <= hi]

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` ops that took most device time in the window, in
        seconds of self time (an op's time less that of the ops nested in
        it, as a loop's body ops are in the loop) summed over their calls
        and chips."""
        lo, hi = self.window
        total: dict[str, float] = defaultdict(float)
        for ops in self.ops.values():
            for o, t in _self_times(ops, lo, hi):
                total[o.short] += t * 1e-9
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest gaps with no op on a chip inside the window,
        each named by the host span open at its middle."""
        lo, hi = self.window
        gaps = []
        for ops in self.ops.values():
            t = lo
            for a, b in _union(ops, lo, hi):
                if a > t:
                    gaps.append((t, a))
                t = max(t, b)
            if hi > t:
                gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_span_at((a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:n]]

    def host_span_at(self, t: float) -> str:
        """Innermost benchmark span open at ``t`` (the window span only
        where no other is)."""
        best = None
        for name, a, b in self.spans:
            if a <= t <= b and name != WINDOW_SPAN:
                if best is None or b - a < best[2] - best[1]:
                    best = (name, a, b)
        return best[0] if best else "none"


def _self_times(ops: list[Op], lo: float, hi: float):
    """(op, self time in ns) of the ops inside [lo, hi]; an op that lies
    within another is its child."""
    inside = sorted((o for o in ops if lo <= o.start and o.end <= hi),
                    key=lambda o: (o.start, -o.end))
    own = {id(o): o.end - o.start for o in inside}
    stack: list[Op] = []
    for o in inside:
        while stack and (o.start >= stack[-1].end or o.end > stack[-1].end):
            stack.pop()
        if stack:
            own[id(stack[-1])] -= o.end - o.start
        stack.append(o)
    return [(o, own[id(o)]) for o in inside]


def _union(ops: list[Op], lo: float, hi: float) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for o in sorted(ops, key=lambda o: o.start):
        a, b = max(o.start, lo), min(o.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(profile) -> Reduced:
    """Device ops and benchmark host spans of one trace.  The window is the
    ``bench.window`` span; a trace without one is refused."""
    ops: dict[str, list[Op]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Op(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(HOST_SPAN_PREFIX))
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                         f"found {len(windows)}")
    return Reduced(window=windows[0], ops=ops, spans=spans)

"""Observability spine tests (``repro.obs``).

Pins the contracts the rest of the system leans on:

* Histogram quantiles within a factor ``GROWTH`` of the true order
  statistic (property-tested), registry thread-safety under concurrent
  ``record()``;
* span tracing: ring capacity, disabled = no events, per-(pid, tid)
  monotonic timestamps after ``merged()`` — including the real thing, a
  multi-process trace collected from spawned PS shard workers;
* ``PSTelemetry`` bit-compatibility: the registry-backed refactor keeps
  ``totals``/``to_resource``/``embedding_odt`` arithmetic exactly as the
  pre-registry implementation (hand-computed expectations);
* the live cost-model bridge and the ``PSClient.close()`` drain span /
  final counters.
"""

from __future__ import annotations

import math
import os
import threading
from collections import defaultdict

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core.profiles import B_O
from repro.core.resources import CPU_CORE
from repro.obs import metrics, scopes, trace
from repro.obs.bridge import apply_measured_odt, snapshot_resources
from repro.ps.client import PSClient
from repro.ps.telemetry import PSTelemetry
from repro.ps.transport import make_transport

DIM = 8


@pytest.fixture()
def obs_enabled():
    """Obs on + clean global buffer/registry, restored afterwards."""
    was = obs.enabled()
    obs.configure(enabled=True)
    trace.BUFFER.drain()
    obs.REGISTRY.reset()
    try:
        yield
    finally:
        obs.configure(enabled=was)
        trace.BUFFER.drain()
        obs.REGISTRY.reset()


def _true_rank_value(values: list[float], q: float) -> float:
    vs = sorted(values)
    rank = min(len(vs) - 1, max(0, math.ceil(q * len(vs)) - 1))
    return vs[rank]


class TestHistogram:
    @given(st.lists(st.floats(min_value=1e-7, max_value=1e7),
                    min_size=1, max_size=200),
           st.floats(min_value=0.0, max_value=1.0))
    @settings(max_examples=60, deadline=None)
    def test_quantile_within_growth_of_order_statistic(self, values, q):
        reg = metrics.Registry("prop", enabled=True)
        h = reg.histogram("x")
        for v in values:
            h.record(v)
        est = h.quantile(q)
        true = _true_rank_value(values, q)
        assert true / metrics.GROWTH - 1e-12 <= est \
            <= true * metrics.GROWTH + 1e-12

    def test_edges(self):
        reg = metrics.Registry("edges", enabled=True)
        h = reg.histogram("x")
        assert h.quantile(0.5) == 0.0          # empty
        for v in (0.0, 5e-10, 1.0, 2.0):       # two land in the floor bucket
            h.record(v)
        assert h.quantile(0.0) == 0.0          # exact min
        assert h.quantile(1.0) == 2.0          # exact max
        assert h.quantile(0.25) == 0.0         # floor bucket → exact min
        assert h.count == 4 and h.min == 0.0 and h.max == 2.0

    def test_disabled_records_nothing(self):
        reg = metrics.Registry("off", enabled=False)
        h, c, g = reg.histogram("h"), reg.counter("c"), reg.gauge("g")
        h.record(1.0), c.inc(), g.set(3.0)
        assert h.count == 0 and c.value == 0.0 and g.value == 0.0

    def test_kind_clash_raises(self):
        reg = metrics.Registry("clash", enabled=True)
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.histogram("x")


class TestRegistryThreadSafety:
    def test_concurrent_record_exact_counts(self):
        reg = metrics.Registry("mt", enabled=True)
        threads, per = 8, 500

        def work(i):
            c = reg.counter("ops")          # shared get-or-create
            h = reg.histogram("lat")
            for k in range(per):
                c.inc()
                h.record(1e-3 * (1 + (i * per + k) % 97))

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(threads)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert reg.counter("ops").value == threads * per
        h = reg.histogram("lat")
        assert h.count == threads * per
        assert sum(h._buckets.values()) == threads * per


class TestTrace:
    def test_ring_capacity(self):
        buf = trace.TraceBuffer(capacity=4)
        for i in range(10):
            buf.add({"ts": i})
        assert [e["ts"] for e in buf.events()] == [6, 7, 8, 9]
        assert buf.drain() and len(buf) == 0

    def test_disabled_span_is_noop(self):
        was = trace.enabled()
        trace.set_enabled(False)
        try:
            trace.BUFFER.drain()
            with trace.span("x") as sp:
                sp.args["k"] = 1            # annotating a noop is safe
            trace.instant("y")
            assert len(trace.BUFFER) == 0
        finally:
            trace.set_enabled(was)

    def test_span_nesting_and_merge_monotonic(self, obs_enabled):
        with trace.span("outer", "t"):
            with trace.span("inner", "t", k=1):
                pass
        trace.instant("mark", "t")
        evs = trace.merged(trace.BUFFER.events())
        names = [e["name"] for e in evs]
        # merged() sorts by ts: outer starts before inner
        assert names == ["outer", "inner", "mark"]
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        assert evs[1]["args"] == {"k": 1}
        assert evs[0]["dur"] >= evs[1]["dur"]

    def test_multiproc_worker_lanes_merge(self, obs_enabled):
        """The acceptance trace shape: spans from the main process AND
        >=2 spawned shard workers, distinct pid lanes, each lane
        monotonically timestamped."""
        tr = make_transport("multiproc")
        try:
            for s in (0, 1):
                tr.add_shard(s, dim=DIM)
                tr.request(s, {"op": "create", "bucket": s,
                               "rows": np.zeros((4, DIM), np.float32)})
                tr.request(s, {"op": "pull",
                               "buckets": np.array([s, s]),
                               "ids": np.array([0, 1])})
            with trace.span("main.work", "test"):
                pass
        finally:
            tr.close()                       # ships worker events back
        evs = trace.merged(trace.BUFFER.events())
        pids = {e["pid"] for e in evs if e.get("ph") != "M"}
        assert os.getpid() in pids
        assert len(pids - {os.getpid()}) >= 2, f"worker lanes missing: {pids}"
        lane_names = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
        assert {"ps-shard-0", "ps-shard-1"} <= lane_names
        shard_spans = [e for e in evs if e["name"].startswith("ps.shard.")]
        assert {e["name"] for e in shard_spans} >= {"ps.shard.create",
                                                    "ps.shard.pull"}
        lanes = defaultdict(list)
        for e in evs:
            if e.get("ph") != "M":
                lanes[(e["pid"], e["tid"])].append(e["ts"])
        assert len(lanes) >= 3
        for lane, ts in lanes.items():
            assert ts == sorted(ts), f"lane {lane} not monotonic"


class TestProfilerSink:
    def test_program_spans_reach_the_profiler_with_the_ring_off(
            self, tmp_path):
        """Under a profiler session the loader's and the collector's
        spans land on the profile's host plane, with the obs ring off
        and left empty."""
        import gc
        import glob

        import jax
        from jax.profiler import ProfileData

        from repro.data import PrefetchLoader

        was = trace.enabled()
        trace.set_enabled(False)
        trace.BUFFER.drain()
        try:
            jax.profiler.start_trace(str(tmp_path))
            try:
                loader = PrefetchLoader([{"x": np.zeros(4)}] * 3, depth=1)
                assert len(list(loader)) == 3
                loader.close()
                gc.collect()
            finally:
                jax.profiler.stop_trace()
            assert len(trace.BUFFER) == 0
        finally:
            trace.set_enabled(was)
        (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
        names = {e.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events}
        assert {scopes.DATA_WAIT, scopes.DATA_PRODUCE, scopes.DATA_PUT_WAIT,
                scopes.PYTHON_GC} <= names

    def test_collection_while_the_ring_is_locked(self, tmp_path):
        """A collection that starts while its thread holds the ring's lock
        (as ``events()`` does while it copies) finishes: the collector's
        hook never takes that lock, with the ring on and a profile
        recording."""
        import gc
        import threading

        import jax

        def collect_under_lock():
            with trace.BUFFER._lock:
                gc.collect()

        was = trace.enabled()
        trace.set_enabled(True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert trace.profiling()
            worker = threading.Thread(target=collect_under_lock, daemon=True)
            worker.start()
            worker.join(timeout=30)
            assert not worker.is_alive(), "collection deadlocked on the ring"
        finally:
            jax.profiler.stop_trace()
            trace.set_enabled(was)

    def test_obs_stays_jax_free(self):
        """Spans and the collector's hook never import jax into a
        process that has not (the PS shard worker's import path)."""
        import subprocess
        import sys

        root = os.path.join(os.path.dirname(__file__), "..")
        code = ("import gc, sys; import repro.obs as obs; "
                "obs.configure(enabled=True); gc.collect()\n"
                "with obs.span('x'): pass\n"
                "sys.exit(1 if 'jax' in sys.modules else 0)")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "PYTHONPATH": os.path.join(root, "src")},
            capture_output=True, timeout=60)
        assert proc.returncode == 0, proc.stderr.decode()


class TestPSTelemetryBitCompat:
    """Hand-computed pins: the registry-backed refactor must reproduce
    the pre-registry arithmetic exactly."""

    def _loaded(self) -> PSTelemetry:
        tel = PSTelemetry(2)
        tel.record("pull", rows=np.array([4, 0]), bytes_=np.array([400, 0]),
                   seconds=0.5, hot_rows=np.array([1, 0]))
        tel.record("pull", rows=np.array([2, 6]),
                   bytes_=np.array([200, 600]), seconds=0.25)
        tel.record("push", rows=np.array([3, 3]),
                   bytes_=np.array([300, 300]), seconds=0.5)
        return tel

    def test_totals(self):
        t = self._loaded().totals()
        assert t["pull"] == {"ops": 2, "rows": 12, "bytes": 1200,
                             "seconds": 0.75, "bandwidth": 1200 / 0.75,
                             "hot_fraction": 1 / 12}
        assert t["push"] == {"ops": 1, "rows": 6, "bytes": 600,
                             "seconds": 0.5, "bandwidth": 600 / 0.5,
                             "hot_fraction": 0.0}

    def test_zero_row_shards_not_counted(self):
        tel = self._loaded()
        # the shard-1 entry of the first pull carried 0 rows: no op there
        assert tel.pull[1].ops == 1 and tel.pull[0].ops == 2

    def test_to_resource(self):
        res = self._loaded().to_resource(CPU_CORE)
        assert res.name == "cpu+ps"
        assert res.ingest_bw == pytest.approx(1200 / 0.75)
        assert res.net_bw == pytest.approx((1200 + 600) / (0.75 + 0.5))
        # unmeasured terms keep the nominal constants
        assert res.flops == CPU_CORE.flops

    def test_to_resource_no_traffic_keeps_base(self):
        res = PSTelemetry(2).to_resource(CPU_CORE)
        assert res.ingest_bw == CPU_CORE.ingest_bw
        assert res.net_bw == CPU_CORE.net_bw

    def test_embedding_odt(self):
        sync, act = self._loaded().embedding_odt(100)
        assert sync == pytest.approx((0.75 + 0.5) / 100 * B_O)
        assert act == pytest.approx(0.75 / 100 * B_O)
        assert PSTelemetry(2).embedding_odt(0) == (0.0, 0.0)

    def test_ensure_grows(self):
        tel = self._loaded()
        tel.ensure(4)
        assert tel.num_shards == 4 and tel.pull[3].ops == 0
        # history stays additive
        assert tel.totals()["pull"]["rows"] == 12


class TestBridge:
    def test_snapshot_with_telemetry(self):
        tel = PSTelemetry(1)
        tel.record("pull", rows=np.array([10]), bytes_=np.array([1000]),
                   seconds=0.1)
        snap = snapshot_resources(CPU_CORE, telemetry=tel, num_examples=10)
        assert snap["resource"].name == "cpu+ps"
        assert snap["resource"].ingest_bw == pytest.approx(1000 / 0.1)
        assert snap["embedding_odt"][1] == pytest.approx(0.1 / 10 * B_O)
        assert snap["ps"]["pull"]["bytes"] == 1000

    def test_snapshot_serve_signals(self, obs_enabled):
        reg = obs.REGISTRY
        reg.gauge("serve.queue_depth").set(3)
        reg.gauge("serve.pool_pages_total").set(28)
        reg.counter("serve.evictions").inc(2)
        for v in (0.1, 0.2, 0.4):
            reg.histogram("serve.ttft_s").record(v)
        snap = snapshot_resources(CPU_CORE)
        assert snap["resource"].name == "cpu+obs"
        sig = snap["serve"]
        assert sig["queue_depth"] == 3 and sig["evictions"] == 2
        assert sig["ttft"]["count"] == 3
        assert 0.1 <= sig["ttft"]["p50"] <= 0.4

    def test_apply_measured_odt(self):
        from repro.core.profiles import LayerProfile

        p = LayerProfile(index=0, kind="embedding", flops=1.0,
                         input_bytes=4.0, weight_bytes=8.0, output_bytes=4.0,
                         oct=(1.0, 2.0), odt_sync=(0.1, 0.1),
                         odt_act=(0.2, 0.2))
        q = apply_measured_odt(p, 0.5, 0.25)
        assert q.odt_sync == (0.5, 0.5) and q.odt_act == (0.25, 0.25)
        assert q.oct == p.oct


class _FakeTable:
    def __init__(self):
        self.pushes = 0

    def pull(self, ids):
        return np.zeros((len(ids), DIM), np.float32)

    def push(self, ids, grads, *, lr, dedup=True):
        self.pushes += 1


class TestClientDrain:
    def test_close_emits_drain_span_and_final_counters(self, obs_enabled):
        table = _FakeTable()
        loader = [{"ids": np.arange(4)} for _ in range(3)]
        client = PSClient(table, loader, depth=2)
        for batch, rows in client:
            client.push(batch["ids"], rows, lr=0.1)
        client.close()
        assert table.pushes == 3
        drains = [e for e in trace.BUFFER.events()
                  if e["name"] == "ps.client.drain"]
        assert len(drains) == 1
        assert drains[0]["args"]["dropped"] == 0
        assert {e["name"] for e in trace.BUFFER.events()} >= {
            "ps.client.pull", "ps.client.push_apply"}
        assert obs.REGISTRY.value("ps.client.steps_pulled") == 3
        assert obs.REGISTRY.value("ps.client.steps_pushed") == 3
        assert obs.REGISTRY.value("ps.client.pushes_dropped") == 0


class TestExportRoundTrip:
    def test_flush_writes_trace_and_metrics(self, obs_enabled, tmp_path):
        obs.configure(run_dir=str(tmp_path))
        try:
            with trace.span("work", "t"):
                obs.REGISTRY.counter("n").inc(5)
            paths = obs.flush()
            from repro.obs import export

            tr = export.read_trace(str(tmp_path))
            assert any(e["name"] == "work" for e in tr["traceEvents"])
            snaps = export.read_metrics(str(tmp_path))
            flat = [m for m in snaps[-1]["registries"]["default"]
                    if m["name"] == "n"]
            assert flat and flat[0]["value"] == 5.0
            assert paths["trace"].endswith("trace.json")
        finally:
            obs._run_dir = None

"""Chaos suite: fault injection, retry masking, failure detection, and
crash-consistent checkpoint/restore.

The contract under test, layer by layer:

* **schedule** — ``parse_schedule``/``FaultRule`` are a deterministic
  failure oracle: same seed + schedule ⇒ the same injections at the
  same requests, so every chaos run is replayable;
* **masking** — every non-``crash`` fault (delay, dropped reply,
  duplicated reply, transient recv error) is absorbed by the transport
  retry layer + server seq-dedup and produces a **bit-exact** loss
  trajectory vs a fault-free run;
* **detection** — a hung worker surfaces as retryable
  :class:`PSShardSlow` before escalating, a dead one as
  :class:`PSShardLost` carrying op/exitcode; the heartbeat notices a
  dead shard within its deadline with no request traffic at all;
* **durability** — killing a bucket's primary *and* backup is only
  survivable through the unified checkpoint: the run restores the
  newest complete step and replays to the fault-free trajectory,
  bit-for-bit.  Checkpoint publication is atomic (staged dirs + a
  ``LATEST`` pointer), so a torn save is never selectable.

The property test (hypothesis, in-repo fallback shim) is the ISSUE's
satellite: random interleaved delay/drop/dup/kill schedules against the
elastic fleet, pinned on post-recovery pulls bit-exact vs a fault-free
oracle and on ownership remaining a partition.
"""

from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint import read_pointer
from repro.ps.elastic import ElasticPSFleet, PSUnrecoverable
from repro.ps.faults import FaultInjector, FaultRule, parse_schedule
from repro.ps.snapshot import (
    FleetCheckpointer, list_checkpoints, load_fleet_checkpoint,
    save_fleet_checkpoint, snapshot_fleet,
)
from repro.ps.transport import (
    InProcTransport, MultiprocTransport, PSShardLost, PSShardSlow,
    RetryPolicy,
)

VOCAB, DIM = 97, 4
HARD_TIMEOUT_S = 300

#: proven masking schedule: every fault kind the retry layer must absorb
MASK_SCHED = ("drop_reply,op=grad,after=10,times=2;"
              "dup_reply,op=pull,after=5,times=2;"
              "recv_error,after=20,times=2;"
              "delay,delay_s=0.001,prob=0.3")

#: correlated loss: both replicas of every bucket die inside one step.
#: ``after`` counts global transport attempts — fleet startup is ~24
#: creates, each sync step ~9 attempts (3 shards), each checkpoint
#: drain +12 — so 170 lands ~step 14, after the step-9 checkpoint.
KILL_BOTH = ("crash,op=grad,shard=0,after=170,times=1;"
             "crash,op=grad,shard=1,after=170,times=1")


@pytest.fixture(autouse=True)
def hard_timeout():
    """SIGALRM per-test ceiling: a wedged shard process fails the test
    instead of wedging the runner."""
    def boom(signum, frame):
        raise TimeoutError(
            f"test exceeded the {HARD_TIMEOUT_S}s hard timeout")

    old = signal.signal(signal.SIGALRM, boom)
    signal.alarm(HARD_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _ctr_cfg():
    from repro.ps.workload import CTRConfig

    return CTRConfig(vocab=5_000, emb_dim=8, slots=8, tower=(32,), batch=64)


def _assert_ownership_partition(fleet):
    stats = fleet.stats()
    live = set(stats["live_shards"])
    hosted = {s: set(rep["buckets"]) for s, rep in stats["shards"].items()}
    for b in range(fleet.spec.num_buckets):
        p = stats["primary"][b]
        assert p in live, f"bucket {b} primary {p} is not live"
        assert b in hosted[p], f"shard {p} does not host its bucket {b}"
        k = stats["backup"][b]
        if k >= 0:
            assert k in live and k != p
            assert b in hosted[k]


class TestSchedule:
    def test_parse_string_round_trip(self):
        rules = parse_schedule(
            "crash,op=grad,shard=1,after=50,times=1;"
            "delay,delay_s=0.01,prob=0.2,until=90")
        assert [r.kind for r in rules] == ["crash", "delay"]
        assert rules[0].op == "grad" and rules[0].shard == 1
        assert rules[0].after == 50 and rules[0].times == 1
        assert rules[1].delay_s == 0.01 and rules[1].prob == 0.2
        assert rules[1].until == 90

    def test_parse_accepts_rules_dicts_none(self):
        assert parse_schedule(None) == []
        rules = parse_schedule([FaultRule("delay", delay_s=1.0),
                                {"kind": "crash", "shard": 0}])
        assert rules[0].delay_s == 1.0 and rules[1].shard == 0

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            parse_schedule("meteor_strike")
        with pytest.raises(ValueError):
            FaultRule("meteor_strike")

    def test_bad_token_rejected(self):
        with pytest.raises(ValueError):
            parse_schedule("delay,oops")

    def test_rule_window_and_budget(self):
        r = FaultRule("delay", op="pull", after=3, until=6, times=2)
        assert not r.matches(2, "pull", 0)      # before the window
        assert r.matches(3, "pull", 0)
        assert not r.matches(3, "grad", 0)      # op filter
        assert not r.matches(6, "pull", 0)      # window closed
        r.fired = 2
        assert not r.matches(4, "pull", 0)      # budget exhausted


def _injector_traffic(schedule, seed):
    """A fixed op sequence through a wrapped in-proc shard; returns the
    injector's fired-injection log."""
    tr = FaultInjector(InProcTransport(), schedule, seed=seed)
    tr.add_shard(0, dim=DIM)
    tr.request(0, {"op": "create", "bucket": 0,
                   "rows": np.zeros((8, DIM), np.float32)})
    try:
        for i in range(40):
            tr.request(0, {"op": "pull", "buckets": np.array([0]),
                           "ids": np.array([i % 8])})
        return list(tr.injections), dict(tr.counters)
    finally:
        tr.close()


class TestInjectorDeterminism:
    def test_same_seed_same_injections(self):
        sched = "delay,prob=0.5,delay_s=0.0;recv_error,after=10,times=2"
        a, _ = _injector_traffic(sched, seed=7)
        b, _ = _injector_traffic(sched, seed=7)
        assert a == b and len(a) > 0

    def test_seed_drives_probabilistic_rules(self):
        sched = "delay,prob=0.5,delay_s=0.0"
        a, _ = _injector_traffic(sched, seed=1)
        b, _ = _injector_traffic(sched, seed=2)
        # deterministic per seed, and a fair coin over 40+ attempts
        # cannot fire on exactly the same subset for both seeds
        assert a != b
        for fires in (a, b):
            assert 0 < len(fires) < 40


class TestRetryMasking:
    """Transport-level: each non-crash kind is absorbed with the state
    bit-identical to a fault-free application."""

    def _one_shard(self, schedule, seed=0):
        tr = FaultInjector(InProcTransport(), schedule, seed=seed)
        tr.add_shard(0, dim=DIM, optimizer="sgd")
        tr.request(0, {"op": "create", "bucket": 0,
                       "rows": np.zeros((8, DIM), np.float32)})
        return tr

    def _grad(self):
        return {"op": "grad", "buckets": np.array([0, 0]),
                "ids": np.array([1, 4]),
                "grads": np.ones((2, DIM), np.float32), "lr": 0.1}

    def test_drop_reply_applies_exactly_once(self):
        # the shard applies the grad, the reply evaporates; the retry is
        # answered from the server's seq cache — never double-applied
        tr = self._one_shard("drop_reply,op=grad,times=1")
        try:
            tr.request(0, self._grad())
            rows = tr.request(0, {"op": "snapshot", "bucket": 0})["rows"]
            assert np.allclose(rows[1], -0.1)   # one application of lr=0.1
            assert tr.counters["retries"] >= 1
            stats = tr.request(0, {"op": "stats"})
            assert stats["counters"]["dedup_replays"] >= 1
        finally:
            tr.close()

    def test_dup_reply_stale_seq_discarded(self):
        tr = self._one_shard("dup_reply,op=pull,times=1")
        try:
            out = tr.request(0, {"op": "pull", "buckets": np.array([0]),
                                 "ids": np.array([2])})
            assert np.array_equal(out["rows"], np.zeros((1, DIM)))
            assert tr.counters["stale_replies"] >= 1
        finally:
            tr.close()

    def test_recv_error_resend_is_first_delivery(self):
        tr = self._one_shard("recv_error,op=grad,times=1")
        try:
            tr.request(0, self._grad())
            rows = tr.request(0, {"op": "snapshot", "bucket": 0})["rows"]
            assert np.allclose(rows[1], -0.1)
            assert tr.counters["retries"] >= 1
            stats = tr.request(0, {"op": "stats"})
            # the request was never delivered twice
            assert stats["counters"]["dedup_replays"] == 0
        finally:
            tr.close()

    def test_crash_surfaces_as_lost_with_shard_ids(self):
        tr = self._one_shard("crash,op=grad,times=1")
        try:
            with pytest.raises(PSShardLost) as ei:
                tr.request(0, self._grad())
            assert ei.value.shard_ids == {0}
            assert 0 not in tr.live_shards
        finally:
            tr.close()

    def test_exhausted_retries_escalate(self):
        tr = FaultInjector(
            InProcTransport(retry=RetryPolicy(max_attempts=2,
                                              backoff_s=0.001)),
            "recv_error", seed=0)   # unbounded: every attempt fails
        tr.add_shard(0, dim=DIM)
        try:
            with pytest.raises(PSShardLost) as ei:
                tr.request(0, {"op": "stats"})
            assert "escalated after 2 attempt(s)" in str(ei.value)
            assert tr.counters["escalations"] == 1
        finally:
            tr.close()


class TestCTRChaosMasking:
    """Workload-level: the ISSUE's acceptance pins, against the elastic
    CTR trainer."""

    KW = dict(steps=30, num_shards=3, optimizer="adagrad", mode="sync")

    def test_masked_schedule_is_bit_exact(self):
        from repro.ps.workload import train_ctr_elastic

        cfg = _ctr_cfg()
        base = train_ctr_elastic(cfg, **self.KW)
        chaotic = train_ctr_elastic(cfg, **self.KW,
                                    fault_schedule=MASK_SCHED, fault_seed=0)
        assert chaotic["injections"], "schedule never fired"
        assert chaotic["transport_counters"]["retries"] >= 1
        np.testing.assert_array_equal(chaotic["losses"], base["losses"])

    def test_single_crash_masked_by_replica_recovery(self):
        from repro.ps.workload import train_ctr_elastic

        cfg = _ctr_cfg()
        base = train_ctr_elastic(cfg, **self.KW)
        hit = train_ctr_elastic(
            cfg, **self.KW, fault_seed=0,
            fault_schedule="crash,op=grad,shard=0,after=100,times=1")
        assert any(i["kind"] == "crash" for i in hit["injections"])
        assert any(e["kind"] == "recover" for e in hit["events"])
        np.testing.assert_array_equal(hit["losses"], base["losses"])

    def test_kill_both_replicas_without_checkpoint_is_fatal(self):
        from repro.ps.workload import train_ctr_elastic

        with pytest.raises(PSUnrecoverable):
            train_ctr_elastic(_ctr_cfg(), **self.KW,
                              fault_schedule=KILL_BOTH, fault_seed=0)

    def test_kill_both_replicas_restores_bit_exact(self, tmp_path):
        """THE tentpole pin: correlated primary+backup loss mid-training
        restores the newest unified checkpoint and replays to the
        fault-free loss trajectory, bit-for-bit."""
        from repro.ps.workload import train_ctr_elastic

        cfg = _ctr_cfg()
        base = train_ctr_elastic(cfg, **self.KW)
        d = str(tmp_path / "ckpt")
        r = train_ctr_elastic(cfg, **self.KW, fault_schedule=KILL_BOTH,
                              fault_seed=0, ckpt_dir=d, ckpt_every=5)
        assert r["restores"] >= 1
        assert sum(i["kind"] == "crash" for i in r["injections"]) == 2
        assert [s for s, _ in r["checkpoints"]] == [4, 9, 14, 19, 24, 29]
        np.testing.assert_array_equal(r["losses"], base["losses"])
        # the checkpoint dir is clean: no staging residue, LATEST valid
        assert not [e for e in os.listdir(d) if ".tmp-" in e]
        latest = read_pointer(d)
        assert latest is not None and os.path.isdir(latest)


def _small_fleet(**kw):
    return ElasticPSFleet(VOCAB, DIM, num_shards=3, num_buckets=6,
                          optimizer=kw.pop("optimizer", "adagrad"), **kw)


class TestCheckpointAtomicity:
    def _push_some(self, fleet, rng, rounds=4):
        for _ in range(rounds):
            ids = rng.integers(0, VOCAB, size=16)
            fleet.push(ids, rng.normal(size=(16, DIM)).astype(np.float32),
                       lr=0.1)

    def test_snapshot_restore_round_trip_bit_exact(self, tmp_path):
        rng = np.random.default_rng(0)
        fleet = _small_fleet()
        try:
            self._push_some(fleet, rng)
            before = np.asarray(fleet.to_dense())
            snap = snapshot_fleet(fleet)
            save_fleet_checkpoint(str(tmp_path), 7, params={"w": before},
                                  snap=snap)
            params, snap2, step, _ = load_fleet_checkpoint(
                str(tmp_path), params_template={"w": before})
            assert step == 7
            np.testing.assert_array_equal(params["w"], before)
            fresh = _small_fleet()
            try:
                fresh.restore_snapshot(snap2)
                np.testing.assert_array_equal(
                    np.asarray(fresh.to_dense()), before)
                _assert_ownership_partition(fresh)
                # the restored optimizer state keeps training identical
                ids = np.arange(8)
                g = np.ones((8, DIM), np.float32)
                fleet.push(ids, g, lr=0.1)
                fresh.push(ids, g, lr=0.1)
                np.testing.assert_array_equal(
                    np.asarray(fresh.to_dense()),
                    np.asarray(fleet.to_dense()))
            finally:
                fresh.close()
        finally:
            fleet.close()

    def test_interrupted_save_is_never_selected(self, tmp_path):
        rng = np.random.default_rng(1)
        fleet = _small_fleet()
        try:
            self._push_some(fleet, rng)
            snap = snapshot_fleet(fleet)
            dense = np.asarray(fleet.to_dense())
            save_fleet_checkpoint(str(tmp_path), 3, params={"w": dense},
                                  snap=snap)
            # a crash mid-write leaves a staging dir and no pointer flip
            orphan = tmp_path / "step-00000004.tmp-999"
            orphan.mkdir()
            (orphan / "manifest.json").write_text("{\"torn\":")
            assert [s for s, _ in list_checkpoints(str(tmp_path))] == [3]
            _, _, step, _ = load_fleet_checkpoint(
                str(tmp_path), params_template={"w": dense})
            assert step == 3
        finally:
            fleet.close()

    def test_prune_keeps_newest_and_sweeps_orphans(self, tmp_path):
        rng = np.random.default_rng(2)
        fleet = _small_fleet()
        try:
            dense = np.asarray(fleet.to_dense())
            for step in (1, 2, 3, 4):
                self._push_some(fleet, rng, rounds=1)
                save_fleet_checkpoint(
                    str(tmp_path), step, params={"w": dense},
                    snap=snapshot_fleet(fleet), keep=2)
            steps = [s for s, _ in list_checkpoints(str(tmp_path))]
            assert steps == [3, 4]
            latest = read_pointer(str(tmp_path))
            assert latest and latest.endswith("step-00000004")
        finally:
            fleet.close()

    def test_checkpointer_cadence_and_order(self, tmp_path):
        rng = np.random.default_rng(3)
        fleet = _small_fleet()
        ckpt = FleetCheckpointer(fleet, str(tmp_path), every=3, keep=0)
        try:
            dense = {"w": np.zeros((2, 2), np.float32)}
            fired = [ckpt.maybe_save(i, dense) for i in range(9)]
            ckpt.wait()
            assert fired == [False, False, True] * 3
            assert [s for s, _ in ckpt.saved] == [2, 5, 8]
            assert [s for s, _ in list_checkpoints(str(tmp_path))] \
                == [2, 5, 8]
        finally:
            ckpt.close()
            fleet.close()

    def test_restore_rejects_mismatched_geometry(self):
        fleet = _small_fleet()
        try:
            snap = snapshot_fleet(fleet)
            snap["meta"]["vocab"] = VOCAB + 1
            with pytest.raises(ValueError):
                fleet.restore_snapshot(snap)
        finally:
            fleet.close()


class TestHungVsDeadMultiproc:
    """The multiproc transport's three failure grades, against real
    worker processes."""

    def test_hung_worker_escalates_with_context(self):
        tr = MultiprocTransport(
            request_timeout=0.5, heartbeat_s=None,
            retry=RetryPolicy(max_attempts=2, backoff_s=0.01))
        tr.add_shard(0, dim=DIM)
        try:
            pid = tr._shards[0].proc.pid
            os.kill(pid, signal.SIGSTOP)
            try:
                with pytest.raises(PSShardLost) as ei:
                    tr.request(0, {"op": "stats"})
            finally:
                try:
                    os.kill(pid, signal.SIGCONT)
                except ProcessLookupError:
                    pass
            msg = str(ei.value)
            # hung (not dead): retried, then escalated with the op name
            # and the alive-at-timeout diagnosis in the chain
            assert "op='stats'" in msg and "process alive" in msg
            assert tr.counters["retries"] >= 1
            assert tr.counters["escalations"] == 1
        finally:
            tr.close()

    def test_dead_worker_reports_exitcode(self):
        tr = MultiprocTransport(heartbeat_s=None)
        tr.add_shard(0, dim=DIM)
        try:
            os.kill(tr._shards[0].proc.pid, signal.SIGKILL)
            time.sleep(0.1)
            with pytest.raises(PSShardLost) as ei:
                tr.request(0, {"op": "stats"})
            assert "exitcode=-9" in str(ei.value)
        finally:
            tr.close()

    def test_heartbeat_detects_death_without_traffic(self):
        lost = []
        tr = MultiprocTransport(heartbeat_s=0.1)
        tr.on_shard_lost = lost.append
        tr.add_shard(0, dim=DIM)
        tr.add_shard(1, dim=DIM)
        try:
            os.kill(tr._shards[0].proc.pid, signal.SIGKILL)
            deadline = time.monotonic() + 5.0
            while 0 in tr.live_shards and time.monotonic() < deadline:
                time.sleep(0.02)
            assert 0 not in tr.live_shards, "heartbeat never noticed"
            assert lost == [0]
            assert tr.counters["heartbeat_misses"] >= 1
            assert 1 in tr.live_shards    # the healthy shard is untouched
        finally:
            tr.close()

    def test_intentional_removal_never_fires_callback(self):
        lost = []
        tr = MultiprocTransport(heartbeat_s=0.05)
        tr.on_shard_lost = lost.append
        for s in (0, 1):
            tr.add_shard(s, dim=DIM)
        try:
            tr.stop_shard(0)
            tr.kill_shard(1)
            time.sleep(0.3)   # several heartbeat periods
            assert lost == []
        finally:
            tr.close()

    def test_hedged_read_wins_over_stall(self):
        tr = MultiprocTransport(request_timeout=10.0, heartbeat_s=None,
                                hedge_s=0.05)
        tr.add_shard(0, dim=DIM)
        try:
            pid = tr._shards[0].proc.pid
            os.kill(pid, signal.SIGSTOP)
            t = threading.Timer(0.3, os.kill, (pid, signal.SIGCONT))
            t.start()
            try:
                out = tr.request(0, {"op": "stats"})
            finally:
                t.cancel()
                try:
                    os.kill(pid, signal.SIGCONT)
                except ProcessLookupError:
                    pass
            assert out["ok"]
            assert tr.counters["hedges"] >= 1
            # the duplicate reply (same op answered twice) must not
            # poison the channel for the next request
            assert tr.request(0, {"op": "stats"})["ok"]
        finally:
            tr.close()


class TestClientFlushFailFast:
    """Satellite pin: a dead pusher thread fails ``flush()`` immediately
    with the pending count — not after the full timeout."""

    class _Table:
        def pull(self, ids):
            return np.zeros((np.asarray(ids).size, DIM), np.float32)

        def push(self, ids, grads, *, lr, dedup=True):
            pass

    def test_dead_pusher_raises_immediately(self):
        from repro.ps.client import _STOP, PSClient

        client = PSClient(self._Table(), iter([]), depth=2)
        try:
            # kill the pusher out from under the client, then queue work
            client._push_q.put(_STOP)
            client._pusher.join(5.0)
            assert not client._pusher.is_alive()
            client.push(np.arange(4), np.ones((4, DIM), np.float32), lr=0.1)
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match=r"1 push\(es\) pending"):
                client.flush(timeout=60.0)
            assert time.monotonic() - t0 < 5.0, "flush spun out the timeout"
        finally:
            client.close(drain=False)

    def test_failed_push_surfaces_with_cause(self):
        class _Boom(self._Table):
            def push(self, ids, grads, *, lr, dedup=True):
                raise ValueError("shard exploded")

        from repro.ps.client import PSClient

        client = PSClient(_Boom(), iter([]), depth=2)
        try:
            client.push(np.arange(4), np.ones((4, DIM), np.float32), lr=0.1)
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="PS push failed"):
                client.flush(timeout=60.0)
            assert time.monotonic() - t0 < 5.0
        finally:
            with pytest.raises(RuntimeError):
                client.close()


class TestHealthBridge:
    def test_fleet_health_reflects_degradation(self):
        from repro.core.resources import CPU_CORE
        from repro.obs.bridge import fleet_health, snapshot_resources

        fleet = _small_fleet()
        try:
            h = fleet_health(fleet)
            assert not h["degraded"] and h["dead_shards"] == []
            fleet.kill(0)
            h = fleet_health(fleet)
            assert h["degraded"] and h["dead_shards"] == [0]
            snap = snapshot_resources(CPU_CORE, fleet=fleet)
            assert snap["ps_health"]["degraded"]
            fleet.recover()
            h = fleet_health(fleet)
            assert not h["degraded"]
            assert h["events"]["recover"] >= 1
        finally:
            fleet.close()


class TestChaosProperty:
    """Satellite: random interleaved fault schedules vs the elastic
    fleet — post-recovery pulls bit-exact vs a fault-free oracle,
    ownership stays a partition."""

    ROUNDS = 10

    def _run(self, schedule, seed):
        rng = np.random.default_rng(seed)
        transport = (FaultInjector(InProcTransport(), schedule, seed=seed)
                     if schedule is not None else None)
        fleet = ElasticPSFleet(VOCAB, DIM, num_shards=3, num_buckets=6,
                               optimizer="adagrad", transport=transport)
        try:
            for _ in range(self.ROUNDS):
                ids = rng.integers(0, VOCAB, size=16)
                fleet.push(ids,
                           rng.normal(size=(16, DIM)).astype(np.float32),
                           lr=0.1)
                fleet.pull(ids[:4])
            if schedule is not None:
                # retire the schedule: the property is about state AFTER
                # the chaos window, and fleet.stats() below is a raw
                # introspection call with no recovery path of its own
                fleet.transport.rules.clear()
            pulled = np.asarray(fleet.pull(np.arange(VOCAB)))
            _assert_ownership_partition(fleet)
            fired = (list(fleet.transport.injections)
                     if schedule is not None else [])
            return pulled, np.asarray(fleet.to_dense()), fired
        finally:
            fleet.close()

    @settings(max_examples=8, deadline=None)
    @given(
        st.integers(min_value=0, max_value=10_000),
        st.lists(st.sampled_from(["delay", "drop_reply", "dup_reply",
                                  "recv_error", "crash"]),
                 min_size=1, max_size=5),
    )
    def test_random_schedules_keep_state_bit_exact(self, seed, kinds):
        rng = np.random.default_rng(seed ^ 0xC0FFEE)
        rules, crashed = [], False
        for kind in kinds:
            if kind == "crash":
                if crashed:    # a second crash could take both replicas
                    continue
                crashed = True
            rules.append(FaultRule(
                kind, after=int(rng.integers(20, 120)), times=1,
                shard=(int(rng.integers(0, 3)) if kind == "crash"
                       else None),
                delay_s=0.0005 if kind == "delay" else 0.0))
        oracle_pull, oracle_dense, _ = self._run(None, seed)
        pull, dense, fired = self._run(rules, seed)
        np.testing.assert_array_equal(pull, oracle_pull)
        np.testing.assert_array_equal(dense, oracle_dense)
        # budget respected: each rule fires at most `times`
        for rule in rules:
            assert sum(1 for f in fired if f["kind"] == rule.kind) \
                <= sum(r.times for r in rules if r.kind == rule.kind)

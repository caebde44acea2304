"""Elastic PS fleet: resharding invariants, bounded staleness, lossless
replica recovery, and the CTR convergence pin.

Property tests (hypothesis, with the in-repo fallback shim) drive random
join/leave/kill sequences interleaved with training traffic and assert
the three invariants the design note promises:

1. **ownership partition** — after any event sequence, every bucket has
   exactly one live primary that actually hosts its rows (checked
   against the shard servers' own bucket lists, not just the client map);
2. **bounded staleness** — a pull against a migrating range never misses
   more than ``staleness_bound`` updates (0 ⇒ never stale at all);
3. **lossless recovery** — after a hard kill, the promoted replica's
   slab is bit-exact vs the lost shard's last acked state.

Plus the ISSUE's acceptance pin: a shard kill + recovery mid-CTR-training
produces the same loss trajectory as the uninterrupted run.
"""

from __future__ import annotations

import signal

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.ps.elastic import BucketSpec, ElasticPSFleet
from repro.ps.transport import PSShardLost

VOCAB, DIM = 97, 4
HARD_TIMEOUT_S = 120


@pytest.fixture(autouse=True)
def hard_timeout():
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


def _push_some(fleet, rng, n=16, lr=0.1):
    ids = rng.integers(0, VOCAB, size=n)
    fleet.push(ids, rng.normal(size=(n, DIM)).astype(np.float32), lr=lr)
    return ids


def _assert_ownership_partition(fleet):
    """Every bucket: exactly one live primary, hosted server-side; the
    buckets' rows partition the vocab."""
    stats = fleet.stats()
    live = set(stats["live_shards"])
    hosted = {s: set(rep["buckets"]) for s, rep in stats["shards"].items()}
    total_rows = 0
    for b in range(fleet.spec.num_buckets):
        p = stats["primary"][b]
        assert p in live, f"bucket {b} primary {p} is not live"
        assert b in hosted[p], f"shard {p} does not host its bucket {b}"
        k = stats["backup"][b]
        if k >= 0:
            assert k in live and k != p
            assert b in hosted[k]
        total_rows += fleet.spec.rows_in(b)
    assert total_rows == fleet.spec.vocab


class TestBucketSpec:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=1, max_value=VOCAB))
    def test_buckets_partition_vocab(self, num_buckets):
        spec = BucketSpec(VOCAB, DIM, num_buckets)
        seen = np.concatenate([spec.global_rows(b)
                               for b in range(num_buckets)])
        assert np.array_equal(np.sort(seen), np.arange(VOCAB))
        ids = np.arange(VOCAB)
        owners = spec.bucket_of(ids)
        for b in range(num_buckets):
            assert np.array_equal(ids[owners == b], spec.global_rows(b))


class TestReshardingInvariants:
    @settings(max_examples=10, deadline=None)
    @given(
        st.integers(min_value=0, max_value=10_000),
        st.lists(st.sampled_from(["join", "leave", "kill"]),
                 min_size=1, max_size=6),
    )
    def test_ownership_partition_after_any_sequence(self, seed, events):
        rng = np.random.default_rng(seed)
        fleet = ElasticPSFleet(VOCAB, DIM, num_shards=3, num_buckets=8,
                               optimizer="sgd")
        try:
            for ev in events:
                _push_some(fleet, rng)
                live = sorted(fleet.transport.live_shards)
                if ev == "join":
                    fleet.join()
                elif ev == "leave" and len(live) > 2:
                    fleet.leave(int(rng.choice(live)))
                elif ev == "kill" and len(live) > 2:
                    fleet.kill(int(rng.choice(live)))
                    fleet.recover()
                _push_some(fleet, rng)
                _assert_ownership_partition(fleet)
            # the table is still fully readable row-for-row
            assert np.asarray(fleet.to_dense()).shape == (VOCAB, DIM)
        finally:
            fleet.close()

    @settings(max_examples=10, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_state_unchanged_by_elasticity(self, seed):
        """The same push stream lands bit-identically whether or not the
        fleet reshapes mid-stream — elasticity is invisible to values."""
        def run(with_events):
            rng = np.random.default_rng(seed)
            fleet = ElasticPSFleet(VOCAB, DIM, num_shards=3, num_buckets=8,
                                   optimizer="adagrad")
            try:
                for i in range(8):
                    _push_some(fleet, rng)
                    if with_events and i == 2:
                        fleet.join()
                    if with_events and i == 5:
                        fleet.kill(0)
                        fleet.recover()
                return np.asarray(fleet.to_dense())
            finally:
                fleet.close()

        assert np.array_equal(run(True), run(False))


class TestBoundedStaleness:
    @settings(max_examples=10, deadline=None)
    @given(
        st.integers(min_value=0, max_value=5),
        st.integers(min_value=1, max_value=12),
        st.integers(min_value=0, max_value=10_000),
    )
    def test_pull_never_staler_than_bound(self, bound, n_pushes, seed):
        rng = np.random.default_rng(seed)
        fleet = ElasticPSFleet(VOCAB, DIM, num_shards=2, num_buckets=4,
                               optimizer="sgd", staleness_bound=bound)
        try:
            sid = fleet.join(rebalance=False)
            fleet.begin_migration(0, sid)
            lr = 0.5
            ids = np.arange(min(5, fleet.spec.bucket_rows))
            for i in range(n_pushes):
                fleet.push(ids, np.ones((ids.size, DIM), np.float32), lr=lr)
                assert fleet.migration_staleness(0) <= bound
                # the true value is -lr per push; the pull may miss at
                # most `bound` of the applied pushes
                seen = float(np.asarray(fleet.pull(ids[:1]))[0, 0])
                true = -lr * (i + 1)
                missed = round((seen - true) / lr)
                assert 0 <= missed <= bound, (seen, true, missed)
            fleet.finish_migration(0)
            assert fleet.migration_backlog(0) == 0
            # after the flip the destination has every update
            seen = float(np.asarray(fleet.pull(ids[:1]))[0, 0])
            assert abs(seen - (-lr * n_pushes)) < 1e-5
            assert fleet.owners()[0][0] == sid
        finally:
            fleet.close()


class TestLosslessRecovery:
    @settings(max_examples=10, deadline=None)
    @given(
        st.integers(min_value=0, max_value=10_000),
        st.sampled_from(["sgd", "adagrad", "adam"]),
    )
    def test_promoted_replica_is_bit_exact(self, seed, optimizer):
        rng = np.random.default_rng(seed)
        fleet = ElasticPSFleet(VOCAB, DIM, num_shards=3, num_buckets=6,
                               optimizer=optimizer)
        try:
            for _ in range(5):
                _push_some(fleet, rng, lr=0.05)
            before = np.asarray(fleet.to_dense())
            victim = int(rng.choice(sorted(fleet.transport.live_shards)))
            fleet.kill(victim)
            # next touch triggers recovery transparently
            after_pull = np.asarray(fleet.pull(np.arange(VOCAB)))
            after = np.asarray(fleet.to_dense())
            assert np.array_equal(before, after)
            assert np.array_equal(before, after_pull)
            _assert_ownership_partition(fleet)
        finally:
            fleet.close()

    def test_losing_both_replicas_is_unrecoverable(self):
        fleet = ElasticPSFleet(VOCAB, DIM, num_shards=2, num_buckets=4,
                               optimizer="sgd")
        fleet.kill(0)
        fleet.kill(1)
        with pytest.raises((RuntimeError, PSShardLost)):
            fleet.recover()

    def test_no_replicas_means_no_recovery(self):
        fleet = ElasticPSFleet(VOCAB, DIM, num_shards=2, num_buckets=4,
                               optimizer="sgd", replicas=0)
        try:
            fleet.kill(0)
            with pytest.raises(RuntimeError):
                fleet.recover()
        finally:
            fleet.close()


class TestCTRConvergencePin:
    def test_kill_recovery_matches_uninterrupted_trajectory(self):
        """ISSUE acceptance: shard kill + replica recovery during CTR
        training converges to the same loss trajectory as the
        uninterrupted run (bit-equal here — sync replication plus a
        deterministic PS-hosted optimizer lose nothing at all)."""
        from repro.ps.workload import CTRConfig, train_ctr_elastic

        cfg = CTRConfig(vocab=5_000, emb_dim=8, slots=8, tower=(32,),
                        batch=64)
        kw = dict(steps=40, num_shards=3, optimizer="sgd", mode="sync")
        calm = train_ctr_elastic(cfg, **kw)
        hit = train_ctr_elastic(
            cfg, **kw, events=[(10, "join", None), (20, "kill", 0)])
        assert any(e["kind"] == "recover" for e in hit["events"])
        assert hit["live_shards"] != calm["live_shards"]
        np.testing.assert_allclose(hit["losses"], calm["losses"],
                                   rtol=0.0, atol=0.0)
        assert np.mean(calm["losses"][-8:]) < np.mean(calm["losses"][:8])

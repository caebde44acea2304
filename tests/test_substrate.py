"""Substrate tests: data pipeline, tier monitor, optimizer, checkpoint,
PS sparse path."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st


from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data import (
    AccessMonitor, PrefetchLoader, SyntheticTokenDataset, Tier, TierThresholds,
)
from repro.optim import adamw_init, adamw_update, clip_by_global_norm
from repro.parallel.ps import segment_rowsum, sparse_pull, sparse_push

KEY = jax.random.PRNGKey(0)


class TestData:
    def test_deterministic_batches(self):
        ds = SyntheticTokenDataset(100, 4, 16, seed=3)
        a, b = ds.batch(7), ds.batch(7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_distinct_steps_differ(self):
        ds = SyntheticTokenDataset(100, 4, 16)
        assert not np.array_equal(ds.batch(0)["tokens"], ds.batch(1)["tokens"])

    def test_labels_are_shifted_tokens(self):
        ds = SyntheticTokenDataset(1000, 2, 8)
        b = ds.batch(0)
        assert b["tokens"].shape == b["labels"].shape == (2, 8)

    def test_prefetch_loader_yields_in_order(self):
        ds = SyntheticTokenDataset(50, 2, 4)
        loader = PrefetchLoader(ds, depth=2)
        got = [next(loader) for _ in range(3)]
        loader.close()
        for i, b in enumerate(got):
            np.testing.assert_array_equal(b["tokens"], ds.batch(i)["tokens"])


class TestTierMonitor:
    def test_hot_rows_go_to_device(self):
        m = AccessMonitor(100, TierThresholds(hot_fraction=0.5,
                                              warm_fraction=0.9))
        m.record(np.array([1] * 100 + [2] * 5 + [3]))
        p = m.placement()
        assert p[1] == Tier.DEVICE
        assert p[50] == Tier.DISK  # never accessed

    def test_aging_decays_counts(self):
        m = AccessMonitor(10)
        m.record(np.array([0, 0, 0]))
        before = m.counts[0]
        m.age()
        assert m.counts[0] < before

    @given(st.lists(st.integers(0, 63), min_size=1, max_size=200))
    @settings(max_examples=20, deadline=None)
    def test_placement_total_partition(self, ids):
        m = AccessMonitor(64)
        m.record(np.array(ids))
        s = m.stats()
        assert s["device_rows"] + s["host_rows"] + s["disk_rows"] == 64


class TestOptim:
    def test_adamw_decreases_quadratic(self):
        params = {"w": jnp.array([3.0, -2.0])}
        opt = adamw_init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, opt = adamw_update(params, grads, opt, lr=0.05)
        assert float(jnp.abs(params["w"]).max()) < 0.1

    def test_clip_scales_to_max_norm(self):
        g = {"a": jnp.full((4,), 10.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(20.0)
        total = jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(clipped)))
        assert float(total) == pytest.approx(1.0, rel=1e-5)

    def test_clip_noop_below_threshold(self):
        g = {"a": jnp.array([0.1, 0.1])}
        clipped, _ = clip_by_global_norm(g, 10.0)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   np.asarray(g["a"]), rtol=1e-6)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        params = {"emb": jax.random.normal(KEY, (10, 4)),
                  "blocks": ({"w": jnp.ones((3, 3))},)}
        opt = adamw_init(params)
        save_checkpoint(str(tmp_path / "ck"), params=params, opt_state=opt,
                        step=17)
        p2, o2, step = load_checkpoint(str(tmp_path / "ck"),
                                       params_template=params,
                                       opt_template=opt)
        assert step == 17
        np.testing.assert_array_equal(np.asarray(params["emb"]),
                                      np.asarray(p2["emb"]))

    def test_shape_mismatch_raises(self, tmp_path):
        params = {"w": jnp.ones((2, 2))}
        save_checkpoint(str(tmp_path / "ck"), params=params)
        with pytest.raises(ValueError):
            load_checkpoint(str(tmp_path / "ck"),
                            params_template={"w": jnp.ones((3, 3))})


class TestSparsePS:
    def test_pull_matches_gather(self):
        table = jax.random.normal(KEY, (20, 8))
        ids = jnp.array([3, 3, 7])
        np.testing.assert_array_equal(np.asarray(sparse_pull(table, ids)),
                                      np.asarray(table[ids]))

    def test_pull_gradient_is_sparse_rowsum(self):
        table = jax.random.normal(KEY, (20, 8))
        ids = jnp.array([3, 3, 7])

        def f(t):
            return jnp.sum(sparse_pull(t, ids) * 2.0)

        g = jax.grad(f)(table)
        assert float(g[3].sum()) == pytest.approx(2.0 * 8 * 2)  # two pulls
        assert float(jnp.abs(g[0]).sum()) == 0.0

    def test_push_updates_only_touched_rows(self):
        table = jnp.zeros((10, 4))
        out = sparse_push(table, jnp.array([2]), jnp.ones((1, 4)), lr=0.5)
        assert float(out[2].sum()) == pytest.approx(-2.0)
        assert float(jnp.abs(out).sum()) == pytest.approx(2.0)

    def test_segment_rowsum_aggregates_duplicates(self):
        g = segment_rowsum(jnp.array([1, 1, 2]), jnp.ones((3, 4)), num_rows=5)
        assert float(g[1].sum()) == pytest.approx(8.0)
        assert float(g[2].sum()) == pytest.approx(4.0)

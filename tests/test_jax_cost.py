"""Equivalence tests: JAX-native soft cost model vs the NumPy oracle.

``jax_cost.soft_cost`` (the fused RL search's reward function) must agree
with ``batched_soft_plan_cost`` on soft cost, true cost, and feasibility
over randomized plans/fleets/jobs.  Documented tolerance (see
``jax_cost`` module docstring): ~1e-9 relative under
``jax.enable_x64(True)`` (the mode the fused scheduler actually
runs in), ~1e-1 on log10-cost in float32 (Newton/ceil rounding can flip
an integer replica count near a boundary).

Also covers ``CostCache.seed_from_device`` (the fused search's bulk
memo-table back-fill) and the layer-padding path used by the vmapped
multi-model search.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (
    INFEASIBLE,
    TrainingJob,
    batched_soft_plan_cost,
    default_fleet,
    jax_cost,
    make_fleet,
    paper_model_profiles,
)
from repro.core.schedulers.base import CostCache

JOB = TrainingJob()
MODELS = ("CTRDNN", "MATCHNET", "2EMB", "NCE")


def _random_plans(rng, n, L, T):
    A = rng.integers(0, T, (n, L))
    A[: min(T, n)] = np.arange(min(T, n))[:, None]   # homogeneous anchors
    if n > T + 1:
        A[T] = np.arange(L) % T                      # max-fragmentation plan
    return A


def _check_x64_equivalence(profiles, fleet, job, A, rel=1e-9):
    bc, soft_np = batched_soft_plan_cost(A, profiles, fleet, job)
    with jax.enable_x64(True):
        soft_j, cost_j, feas_j = jax_cost.jnp_soft_plan_cost(
            A, profiles, fleet, job
        )
    np.testing.assert_array_equal(feas_j, bc.feasible)
    np.testing.assert_array_equal(np.isfinite(cost_j), np.isfinite(bc.costs))
    fin = np.isfinite(bc.costs)
    np.testing.assert_allclose(cost_j[fin], bc.costs[fin], rtol=rel)
    np.testing.assert_allclose(soft_j, soft_np, rtol=rel)


class TestX64Equivalence:
    @pytest.mark.parametrize(
        "model,num_types", [("CTRDNN", 2), ("MATCHNET", 2), ("2EMB", 3), ("NCE", 4)]
    )
    def test_randomized_plans(self, model, num_types):
        fleet = default_fleet() if num_types == 2 else make_fleet(num_types)
        profiles = paper_model_profiles(model, fleet)
        rng = np.random.default_rng(hash((model, num_types)) % 2**32)
        A = _random_plans(rng, 48, len(profiles), num_types)
        _check_x64_equivalence(profiles, fleet, JOB, A)

    @given(
        st.sampled_from(MODELS),
        st.integers(2, 5),
        st.floats(min_value=5e3, max_value=2e6),
        st.sampled_from([256, 4096, 65536]),
        st.integers(0, 10_000),
    )
    @settings(max_examples=12, deadline=None)
    def test_hypothesis_randomized(self, model, num_types, limit, bs, seed):
        """Property: the jnp path agrees with the oracle for any model,
        fleet size, throughput limit (spanning all-feasible through
        mostly-infeasible), and batch size."""
        fleet = default_fleet() if num_types == 2 else make_fleet(num_types)
        profiles = paper_model_profiles(model, fleet)
        job = dataclasses.replace(JOB, throughput_limit=limit, batch_size=bs)
        rng = np.random.default_rng(seed)
        A = _random_plans(rng, 16, len(profiles), num_types)
        _check_x64_equivalence(profiles, fleet, job, A)

    def test_resource_limit_edge(self):
        """Per-type limits small enough that integer rounding decides
        feasibility (Formula 10 boundary)."""
        fleet = [
            dataclasses.replace(r, max_count=max(2, r.max_count // 80))
            for r in default_fleet()
        ]
        profiles = paper_model_profiles("NCE", fleet)
        for limit in (5_000.0, 50_000.0, 200_000.0):
            job = dataclasses.replace(JOB, throughput_limit=limit)
            rng = np.random.default_rng(int(limit))
            A = _random_plans(rng, 16, len(profiles), len(fleet))
            _check_x64_equivalence(profiles, fleet, job, A)


class TestF32Tolerance:
    def test_f32_log_cost_agreement(self):
        """Without x64, agreement is loose but bounded: integer-rounding
        flips can move a replica count by one, so individual soft costs
        drift up to ~20% — but log10-cost (the actual RL reward) stays
        within 0.5 everywhere and within 0.01 for most plans."""
        fleet = default_fleet()
        profiles = paper_model_profiles("MATCHNET", fleet)
        rng = np.random.default_rng(3)
        A = _random_plans(rng, 64, len(profiles), len(fleet))
        _, soft_np = batched_soft_plan_cost(A, profiles, fleet, JOB)
        soft_j, _, _ = jax_cost.jnp_soft_plan_cost(A, profiles, fleet, JOB)
        logdiff = np.abs(np.log10(soft_np) - np.log10(soft_j))
        assert logdiff.max() < 0.5
        assert np.median(logdiff) < 0.01


class TestLayerPadding:
    def test_padded_matches_unpadded(self):
        """Padding NCE (L=5) to 16 layer slots with garbage tail actions
        must not change any cost (the vmapped multi-model contract)."""
        fleet = default_fleet()
        profiles = paper_model_profiles("NCE", fleet)
        rng = np.random.default_rng(5)
        A = _random_plans(rng, 24, 5, 2)
        with jax.enable_x64(True):
            soft_u, cost_u, feas_u = jax_cost.jnp_soft_plan_cost(
                A, profiles, fleet, JOB
            )
            ct = jax_cost.cost_tensors(profiles, fleet, JOB, pad_to=16)
            tail = rng.integers(0, 2, (24, 11))
            out = jax_cost._soft_cost_jit(
                ct, jnp.asarray(np.concatenate([A, tail], axis=1), jnp.int32)
            )
        np.testing.assert_allclose(np.asarray(out.soft), soft_u, rtol=1e-12)
        np.testing.assert_array_equal(np.asarray(out.feasible), feas_u)

    def test_pad_to_too_small_rejected(self):
        fleet = default_fleet()
        profiles = paper_model_profiles("NCE", fleet)
        with pytest.raises(ValueError):
            jax_cost.cost_tensors(profiles, fleet, JOB, pad_to=3)


class TestSeedFromDevice:
    def setup_method(self):
        self.fleet = default_fleet()
        self.profiles = paper_model_profiles("2EMB", self.fleet)
        self.L = len(self.profiles)

    def test_fills_both_memos_and_counts_novel_once(self):
        cache = CostCache(self.profiles, self.fleet, JOB)
        a, b = (0,) * self.L, (1,) * self.L
        n = cache.seed_from_device(
            [a, b, a], [3.0, 5.0, 3.0], [True, True, True]
        )
        assert n == 2 and cache.evaluations == 2
        assert cache(a) == 3.0 and cache.soft(a) == 3.0
        # repeat insert: nothing new, accounting unchanged
        assert cache.seed_from_device([a, b], [9.9, 9.9], [True, True]) == 0
        assert cache.evaluations == 2 and cache(a) == 3.0

    def test_infeasible_gets_inf_true_cost(self):
        cache = CostCache(self.profiles, self.fleet, JOB)
        a = (0,) * self.L
        cache.seed_from_device([a], [7.5], [False])
        assert cache(a) == INFEASIBLE and cache.soft(a) == 7.5

    def test_never_overwrites_oracle_entries(self):
        cache = CostCache(self.profiles, self.fleet, JOB)
        a = (1,) * self.L
        exact = cache(a)  # NumPy-oracle evaluation
        n0 = cache.evaluations
        cache.seed_from_device([a], [exact * 1.001], [math.isfinite(exact)])
        assert cache(a) == exact and cache.evaluations == n0
        if math.isfinite(exact):
            assert cache.soft(a) == exact

    def test_best_sees_device_scored_plans(self):
        cache = CostCache(self.profiles, self.fleet, JOB)
        good, bad, infeas = (0,) * self.L, (1,) * self.L, (0, 1) * (self.L // 2)
        cache.seed_from_device(
            [good, bad, infeas], [1.0, 2.0, 0.5], [True, True, False]
        )
        plan, cost = cache.best()
        assert plan == good and cost == 1.0  # infeasible 0.5 not preferred

    def test_soft_only_mode(self):
        cache = CostCache(self.profiles, self.fleet, JOB)
        a = (0,) * self.L
        cache.seed_from_device([a], [4.0])
        assert cache.soft(a) == 4.0 and cache.evaluations == 1

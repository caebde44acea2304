"""Unit + property tests for the HeterPS cost model (Formulas 1–7)."""

import math

import pytest
from hypothesis import given, settings, strategies as st


from repro.core import (
    INFEASIBLE, SchedulingPlan, TrainingJob, build_stages, default_fleet,
    monetary_cost, paper_model_profiles, pipeline_throughput, plan_cost,
)
from repro.core.cost_model import (
    stage_comm_time, stage_compute_time, stage_exec_time, stage_throughput,
)
from repro.core.plan import ProvisioningPlan
from repro.core.profiles import PAPER_MODELS, ctrdnn_variant, profile_layers

FLEET = default_fleet()
JOB = TrainingJob()


def _stages(model="CTRDNN", plan=None):
    profs = paper_model_profiles(model, FLEET)
    plan = plan or SchedulingPlan((0,) + (1,) * (len(profs) - 1))
    return plan, profs, build_stages(plan, profs, FLEET)


class TestStageFusion:
    def test_consecutive_same_type_layers_fuse(self):
        plan = SchedulingPlan((0, 0, 1, 1, 1, 0))
        assert plan.stage_boundaries() == [(0, 2, 0), (2, 5, 1), (5, 6, 0)]

    def test_all_same_type_is_one_stage(self):
        plan = SchedulingPlan((1,) * 16)
        assert len(plan.stage_boundaries()) == 1

    def test_stage_oct_sums_layer_octs(self):
        plan, profs, stages = _stages()
        assert stages[0].oct == pytest.approx(profs[0].oct[0])
        assert stages[1].oct == pytest.approx(sum(p.oct[1] for p in profs[1:]))

    def test_interior_activation_handoff_not_counted(self):
        """Fusing layers must drop interior activation transfer (§1)."""
        profs = paper_model_profiles("CTRDNN", FLEET)
        fused = build_stages(SchedulingPlan((1,) * 16), profs, FLEET)
        split = build_stages(
            SchedulingPlan(tuple([1] * 15 + [0])), profs, FLEET
        )
        # fused single stage comm < sum of per-layer odt (activations dropped)
        assert fused[0].odt < sum(p.odt[1] for p in profs)


class TestAmdahl:
    def test_more_replicas_never_slower(self):
        _, _, stages = _stages()
        s = stages[1]
        times = [stage_exec_time(s, k, JOB.batch_size) for k in (1, 2, 4, 8, 64)]
        assert all(a >= b for a, b in zip(times, times[1:]))

    def test_sequential_fraction_is_asymptote(self):
        _, _, stages = _stages()
        s = stages[1]
        t_inf = stage_compute_time(s, 10**9, JOB.batch_size)
        expected = (s.oct / 64) * JOB.batch_size * (1 - s.alpha)
        assert t_inf == pytest.approx(expected, rel=1e-3)

    def test_exec_time_is_max_of_compute_and_comm(self):
        _, _, stages = _stages()
        for s in stages:
            for k in (1, 3, 7):
                assert stage_exec_time(s, k, 4096) == pytest.approx(
                    max(stage_compute_time(s, k, 4096),
                        stage_comm_time(s, k, 4096))
                )


class TestThroughputAndCost:
    def test_pipeline_throughput_is_min_over_stages(self):
        plan, profs, stages = _stages()
        prov = ProvisioningPlan(k=(4, 2))
        tps = [stage_throughput(s, k, JOB.batch_size)
               for s, k in zip(stages, prov.k)]
        assert pipeline_throughput(stages, prov, JOB.batch_size) == min(tps)

    def test_resource_limit_violation_is_infeasible(self):
        plan, profs, _ = _stages()
        prov = ProvisioningPlan(k=(10**6, 1))
        assert monetary_cost(plan, prov, profs, FLEET, JOB) == INFEASIBLE

    def test_throughput_violation_is_infeasible(self):
        plan, profs, _ = _stages()
        prov = ProvisioningPlan(k=(1, 1))  # 1 CPU core can't hit 200k ex/s
        assert monetary_cost(plan, prov, profs, FLEET, JOB) == INFEASIBLE

    def test_cpu_only_infeasible_for_ctrdnn(self):
        """Paper Fig. 10: CPU cannot meet the constraint for CTRDNN."""
        profs = paper_model_profiles("CTRDNN", FLEET)
        cost, _ = plan_cost(SchedulingPlan((0,) * 16), profs, FLEET, JOB)
        assert cost == INFEASIBLE

    def test_heterogeneous_beats_gpu_only(self):
        """Paper §6.2: scheduling the embedding to CPU beats GPU-only."""
        profs = paper_model_profiles("CTRDNN", FLEET)
        gpu, _ = plan_cost(SchedulingPlan((1,) * 16), profs, FLEET, JOB)
        het, _ = plan_cost(SchedulingPlan((0,) + (1,) * 15), profs, FLEET, JOB)
        assert het < gpu

    @given(st.lists(st.integers(0, 1), min_size=16, max_size=16))
    @settings(max_examples=30, deadline=None)
    def test_cost_nonnegative_or_infeasible(self, assignment):
        profs = paper_model_profiles("CTRDNN", FLEET)
        cost, prov = plan_cost(SchedulingPlan(tuple(assignment)), profs, FLEET, JOB)
        assert cost == INFEASIBLE or cost > 0
        if prov is not None:
            assert all(k >= 1 for k in prov.k)

    @given(st.sampled_from(sorted(PAPER_MODELS)))
    @settings(max_examples=8, deadline=None)
    def test_every_paper_model_has_feasible_plan(self, model):
        profs = paper_model_profiles(model, FLEET)
        cost, _ = plan_cost(
            SchedulingPlan(tuple(0 if p.kind == "embedding" else 1
                                 for p in profs)),
            profs, FLEET, JOB,
        )
        assert math.isfinite(cost)


class TestVariants:
    @pytest.mark.parametrize("n", [8, 12, 16, 20])
    def test_ctrdnn_variant_layer_counts(self, n):
        assert len(ctrdnn_variant(n)) == n

    def test_variant_profiles_build(self):
        profs = profile_layers(ctrdnn_variant(12), FLEET)
        assert len(profs) == 12 and all(len(p.oct) == 2 for p in profs)

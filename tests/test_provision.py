"""Provisioning tests (§5.1: load balance + Newton + static baselines)."""

import math

import pytest
from hypothesis import given, settings, strategies as st


from repro.core import (
    SchedulingPlan, TrainingJob, build_stages, default_fleet,
    monetary_cost, paper_model_profiles, pipeline_throughput,
)
from repro.core.provision import provision, provision_sta_ratio, required_k

FLEET = default_fleet()
JOB = TrainingJob()


def _stages(plan=None):
    profs = paper_model_profiles("CTRDNN", FLEET)
    plan = plan or SchedulingPlan((0,) + (1,) * 15)
    return plan, profs, build_stages(plan, profs, FLEET)


class TestRequiredK:
    def test_monotone_in_throughput(self):
        _, _, stages = _stages()
        s = stages[0]
        ks = [required_k(s, t, 4096) for t in (1e4, 5e4, 1e5, 2e5)]
        assert all(a <= b for a, b in zip(ks, ks[1:]))

    def test_amdahl_ceiling_is_infeasible(self):
        """No replica count can beat the sequential fraction (Formula 13)."""
        _, _, stages = _stages()
        s = stages[0]
        ceiling = 64 / (s.oct * (1 - s.alpha))  # examples/s asymptote
        assert math.isinf(required_k(s, ceiling * 1.01, 4096))
        assert math.isfinite(required_k(s, ceiling * 0.9, 4096))


class TestProvision:
    def test_meets_throughput_constraint(self):
        plan, profs, stages = _stages()
        prov = provision(stages, FLEET, JOB)
        assert prov is not None
        assert pipeline_throughput(stages, prov, JOB.batch_size) >= JOB.throughput_limit

    def test_load_balance_no_gross_straggler(self):
        """§5.1: stage throughputs should be near-equal (≤ the integer
        rounding gap)."""
        from repro.core.cost_model import stage_throughput

        plan, profs, stages = _stages()
        prov = provision(stages, FLEET, JOB)
        tps = [stage_throughput(s, k, JOB.batch_size)
               for s, k in zip(stages, prov.k)]
        # the bottleneck stage is within ~2x of the fastest stage when its
        # k could still be decremented (integer effects allowed)
        assert min(tps) >= JOB.throughput_limit

    def test_ps_cores_added_for_accelerator_stages(self):
        plan, profs, stages = _stages()
        prov = provision(stages, FLEET, JOB)
        assert prov.ps_cores >= 1  # GPU stage present → PS cores

    def test_infeasible_job_returns_none(self):
        plan, profs, stages = _stages(SchedulingPlan((0,) * 16))
        assert provision(stages, FLEET, JOB) is None

    def test_beats_static_ratio_baselines(self):
        """Paper Fig. 4: our provisioning costs ≤ StaRatio/StaPSRatio."""
        plan, profs, stages = _stages()
        ours = provision(stages, FLEET, JOB)
        c_ours = monetary_cost(plan, ours, profs, FLEET, JOB)
        for with_ps in (False, True):
            sta = provision_sta_ratio(stages, FLEET, JOB, with_ps=with_ps)
            if sta is None:
                continue
            c_sta = monetary_cost(plan, sta, profs, FLEET, JOB)
            if math.isfinite(c_sta):
                assert c_ours <= c_sta * 1.001

    @given(st.floats(min_value=1e4, max_value=4e5))
    @settings(max_examples=20, deadline=None)
    def test_feasible_whenever_constraint_reachable(self, limit):
        plan, profs, stages = _stages()
        job = TrainingJob(throughput_limit=limit)
        prov = provision(stages, FLEET, job)
        if prov is not None:
            assert pipeline_throughput(stages, prov, job.batch_size) >= limit

"""Policies (busy/idle/hybrid/prediction) + Algorithm 2 mechanics."""

from hypothesis import given, settings, strategies as st

from repro.core.manager import WorkerManager
from repro.core.monitoring import TaskMonitor
from repro.core.governor import GovernorSpec, ResourceGovernor, \
    registered_policies
from repro.core.policies import (BusyPolicy, HybridPolicy, IdlePolicy,
                                 PollDecision, PredictionPolicy)
from repro.core.prediction import CPUPredictor, PredictionConfig


def test_busy_never_idles():
    p = BusyPolicy()
    for spin in range(1000):
        assert p.on_poll_empty(0, 8, spin) is PollDecision.SPIN


def test_idle_immediately():
    p = IdlePolicy()
    assert p.on_poll_empty(0, 8, 1) is PollDecision.IDLE
    assert p.workers_to_resume(active=2, idle=6, ready_tasks=4) == 2


def test_hybrid_budget_boundary():
    p = HybridPolicy(spin_budget=100)
    assert p.on_poll_empty(0, 8, 99) is PollDecision.SPIN
    assert p.on_poll_empty(0, 8, 100) is PollDecision.IDLE


def _predictor_with_delta(delta: int, n: int = 16) -> CPUPredictor:
    m = TaskMonitor(min_samples=1)
    # α = rate ⇒ each live task ⇒ one CPU-window of work
    for i in range(3):
        m.on_task_ready(i, "t", 1.0)
        m.on_task_execute(i, "t", 1.0)
        m.on_task_completed(i, "t", 1.0, 50e-6)
    for i in range(delta):
        m.on_task_ready(100 + i, "t", 1.0)
    p = CPUPredictor(m, n_cpus=n, config=PredictionConfig(
        rate_s=50e-6, min_samples=1))
    p.tick()
    assert p.delta == delta
    return p


class TestAlgorithm2:
    def test_poll_idles_only_above_delta(self):
        pred = _predictor_with_delta(4)
        pol = PredictionPolicy(pred)
        assert pol.on_poll_empty(0, active=5, spin_count=1) \
            is PollDecision.IDLE
        assert pol.on_poll_empty(0, active=4, spin_count=99) \
            is PollDecision.SPIN

    def test_resume_up_to_delta(self):
        pred = _predictor_with_delta(6)
        pol = PredictionPolicy(pred)
        assert pol.workers_to_resume(active=2, idle=10, ready_tasks=9) == 4
        assert pol.workers_to_resume(active=6, idle=10, ready_tasks=9) == 0

    def test_manager_delta_transitions(self):
        pred = _predictor_with_delta(2)
        mgr = WorkerManager(4, PredictionPolicy(pred), clock=lambda: 0.0)
        # All four workers spin; two empty polls should idle two of them
        assert mgr.poll_empty(0) is PollDecision.IDLE   # δ 4 > 2
        assert mgr.poll_empty(1) is PollDecision.IDLE   # δ 3 > 2
        assert mgr.poll_empty(2) is PollDecision.SPIN   # δ 2 == Δ
        assert mgr.active == 2
        # Work arrives; Δ=2 already met ⇒ no resumes
        assert mgr.notify_added(5) == []

    def test_manager_counts_transitions(self):
        mgr = WorkerManager(2, IdlePolicy(), clock=lambda: 0.0)
        mgr.poll_empty(0)
        mgr.poll_empty(1)
        assert mgr.idles == 2
        woken = mgr.notify_added(2)
        assert sorted(woken) == [0, 1]
        assert mgr.resumes == 2

    def test_target_capped_at_owned_resources(self):
        """Regression: an oversubscribing predictor (the DLB Alg.-1
        variant) must not let a non-sharing pull-style frontend scale
        beyond what it owns."""
        m = TaskMonitor(min_samples=1)
        for i in range(3):
            m.on_task_ready(i, "t", 1.0)
            m.on_task_execute(i, "t", 1.0)
            m.on_task_completed(i, "t", 1.0, 50e-6)
        for i in range(10):                     # Δ would be 10
            m.on_task_ready(100 + i, "t", 1.0)
        pred = CPUPredictor(m, n_cpus=4, config=PredictionConfig(
            rate_s=50e-6, min_samples=1, allow_oversubscription=True,
            oversubscription_cap=4.0))
        pred.tick()
        assert pred.delta == 10                 # oversubscribed Δ
        pol = PredictionPolicy(pred)
        assert pol.target(queued=10, active=0, n_resources=4) == 4
        assert pol.target(queued=0, active=0, n_resources=4) == 0


@given(active=st.integers(0, 64), idle=st.integers(0, 64),
       ready=st.integers(0, 256), delta=st.integers(1, 64))
@settings(max_examples=200, deadline=None)
def test_prediction_resume_invariants(active, idle, ready, delta):
    """Property: resumes never exceed idle count, ready tasks, or Δ−δ."""
    pred = _predictor_with_delta(delta, n=64)
    pol = PredictionPolicy(pred)
    n = pol.workers_to_resume(active, idle, ready)
    assert 0 <= n <= idle
    assert n <= max(0, delta - active)
    assert n <= ready


def test_registry_factory():
    def build(name, **kw):
        return ResourceGovernor(GovernorSpec(resources=8, policy=name,
                                             **kw)).policy

    assert build("busy").name == "busy"
    assert build("idle").name == "idle"
    assert build("hybrid", spin_budget=5).spin_budget == 5
    pred_policy = build("prediction")
    assert pred_policy.uses_predictions
    assert pred_policy.predictor is not None   # governor supplied it
    for name in ("busy", "idle", "hybrid", "prediction",
                 "dlb-lewi", "dlb-hybrid", "dlb-prediction"):
        assert name in registered_policies()

"""Algorithm 1 — Δ prediction."""

from hypothesis import given, settings, strategies as st

from repro.core.monitoring import TaskMonitor
from repro.core.prediction import CPUPredictor, PredictionConfig


def _seed_alpha(m: TaskMonitor, type_name: str, unitary: float,
                n: int = 6, cost: float = 1.0) -> None:
    for i in range(n):
        tid = hash((type_name, i)) % 10**9
        m.on_task_ready(tid, type_name, cost)
        m.on_task_execute(tid, type_name, cost)
        m.on_task_completed(tid, type_name, cost, unitary * cost)


class TestAlgorithm1:
    def test_delta_matches_workload(self):
        """48 tasks of 50 µs with f = 50 µs ⇒ γ = 48 ⇒ Δ = 48."""
        m = TaskMonitor(min_samples=3)
        _seed_alpha(m, "t", 50e-6)
        for i in range(48):
            m.on_task_ready(1000 + i, "t", 1.0)
        p = CPUPredictor(m, n_cpus=48,
                         config=PredictionConfig(rate_s=50e-6,
                                                 min_samples=3))
        assert p.compute_delta() == 48

    def test_delta_scales_with_granularity(self):
        """Half the work per window ⇒ half the CPUs (the adaptiveness
        to granularity of §3.2)."""
        m = TaskMonitor(min_samples=3)
        _seed_alpha(m, "t", 25e-6)           # 25 µs tasks
        for i in range(48):
            m.on_task_ready(1000 + i, "t", 1.0)
        p = CPUPredictor(m, n_cpus=48,
                         config=PredictionConfig(rate_s=50e-6,
                                                 min_samples=3))
        assert p.compute_delta() == 24

    def test_count_fallback_when_unreliable(self):
        """Too few samples ⇒ count-based Δ (coarse Cholesky behaviour)."""
        m = TaskMonitor(min_samples=100)
        for i in range(5):
            m.on_task_ready(i, "t", 123.0)
        p = CPUPredictor(m, n_cpus=48,
                         config=PredictionConfig(min_samples=100))
        assert p.compute_delta() == 5

    def test_delta_at_least_one_when_idle(self):
        m = TaskMonitor()
        p = CPUPredictor(m, n_cpus=8)
        assert p.compute_delta() == 1        # Alg 1: 0 < Δ

    def test_oversubscription_allowed_in_dlb_mode(self):
        m = TaskMonitor(min_samples=3)
        _seed_alpha(m, "t", 50e-6)
        for i in range(100):
            m.on_task_ready(1000 + i, "t", 1.0)
        p_local = CPUPredictor(m, n_cpus=8,
                               config=PredictionConfig(rate_s=50e-6,
                                                       min_samples=3))
        p_dlb = CPUPredictor(m, n_cpus=8, config=PredictionConfig(
            rate_s=50e-6, min_samples=3, allow_oversubscription=True))
        assert p_local.compute_delta() == 8
        assert p_dlb.compute_delta() > 8     # paper §3.3

    @given(n_cpus=st.integers(1, 256),
           tasks=st.lists(st.tuples(st.floats(1e-6, 1.0),
                                    st.integers(1, 50)),
                          min_size=0, max_size=10))
    @settings(max_examples=150, deadline=None)
    def test_invariant_bounds(self, n_cpus, tasks):
        """Property (Alg 1 Ensure): 1 ≤ Δ ≤ min(N_CPUs, ΣM_j) when work
        exists; Δ = 1 when idle."""
        m = TaskMonitor(min_samples=2)
        total = 0
        for j, (unitary, count) in enumerate(tasks):
            _seed_alpha(m, f"t{j}", unitary, n=3)
            for i in range(count):
                m.on_task_ready(10_000 + 100 * j + i, f"t{j}", 1.0)
            total += count
        p = CPUPredictor(m, n_cpus=n_cpus,
                         config=PredictionConfig(min_samples=2))
        d = p.compute_delta()
        if total == 0:
            assert d == 1
        else:
            assert 1 <= d <= min(n_cpus, total)

    def test_tick_publishes_atomically(self):
        m = TaskMonitor(min_samples=1)
        _seed_alpha(m, "t", 1e-3)
        for i in range(4):
            m.on_task_ready(100 + i, "t", 1.0)
        p = CPUPredictor(m, n_cpus=16)
        before = p.delta
        assert before == 16                  # optimistic start
        p.tick()
        assert p.delta == p.compute_delta()
        assert p.predictions_made == 1

"""Monitoring infrastructure (paper §3.1): EMA, workload accounting,
accuracy, parent–child subtraction."""

import math

from hypothesis import given, settings, strategies as st

from repro.core.monitoring import EMA, TaskMonitor


class TestEMA:
    def test_warmup_is_mean(self):
        e = EMA(decay=0.5, warmup=3)
        for v in (1.0, 2.0, 3.0):
            e.update(v)
        assert math.isclose(e.value, 2.0)

    def test_post_warmup_tracks_recent(self):
        e = EMA(decay=0.5, warmup=1)
        for v in [1.0] * 5 + [10.0] * 20:
            e.update(v)
        assert 9.0 < e.value <= 10.0

    @given(st.lists(st.floats(min_value=0.001, max_value=1e6),
                    min_size=1, max_size=200),
           st.floats(min_value=0.01, max_value=1.0))
    @settings(max_examples=200, deadline=None)
    def test_bounded_by_range(self, samples, decay):
        """EMA stays within [min, max] of its inputs — any decay."""
        e = EMA(decay=decay, warmup=4)
        for s in samples:
            e.update(s)
        assert min(samples) - 1e-9 <= e.value <= max(samples) + 1e-9

    @given(st.lists(st.floats(min_value=0.5, max_value=2.0),
                    min_size=8, max_size=64))
    @settings(max_examples=100, deadline=None)
    def test_reliability_monotone(self, samples):
        e = EMA()
        for i, s in enumerate(samples):
            e.update(s)
            assert e.reliable(i + 1)
            assert not e.reliable(i + 2)


class TestWorkloadAccounting:
    def test_lifecycle_conserves(self):
        m = TaskMonitor(min_samples=1)
        m.on_task_ready(1, "t", 10.0)
        m.on_task_ready(2, "t", 5.0)
        snap = dict((n, (w, mm)) for n, w, _a, mm, _r
                    in m.workload_snapshot())
        assert snap["t"] == (15.0, 2)
        m.on_task_execute(1, "t", 10.0)
        snap = m.workload_snapshot()[0]
        assert snap[1] == 15.0 and snap[3] == 2   # still live
        m.on_task_completed(1, "t", 10.0, elapsed=1.0)
        snap = m.workload_snapshot()[0]
        assert snap[1] == 5.0 and snap[3] == 1
        m.on_task_execute(2, "t", 5.0)
        m.on_task_completed(2, "t", 5.0, elapsed=0.5)
        assert m.workload_snapshot() == []
        assert m.completed_instances() == 2

    def test_unitary_cost_normalizes_across_sizes(self):
        """Tasks of different cost but equal per-unit speed share α."""
        m = TaskMonitor(min_samples=1)
        for tid, (cost, elapsed) in enumerate(
                [(10.0, 1.0), (20.0, 2.0), (40.0, 4.0)]):
            m.on_task_ready(tid, "gemm", cost)
            m.on_task_execute(tid, "gemm", cost)
            m.on_task_completed(tid, "gemm", cost, elapsed)
        assert math.isclose(m.unitary_cost("gemm"), 0.1, rel_tol=1e-9)

    def test_accuracy_perfect_prediction(self):
        m = TaskMonitor(min_samples=1)
        # seed α = 0.1 s/unit
        m.on_task_ready(0, "t", 10.0)
        m.on_task_execute(0, "t", 10.0)
        m.on_task_completed(0, "t", 10.0, 1.0)
        # next instance matches the prediction exactly
        m.on_task_ready(1, "t", 10.0)
        m.on_task_execute(1, "t", 10.0)
        m.on_task_completed(1, "t", 10.0, 1.0)
        rep = m.accuracy_report()
        assert rep.instances == 1
        assert math.isclose(rep.average_pct, 100.0)

    def test_accuracy_na_when_no_predictions(self):
        m = TaskMonitor(min_samples=100)    # α never reliable
        for tid in range(5):
            m.on_task_ready(tid, "t", 1.0)
            m.on_task_execute(tid, "t", 1.0)
            m.on_task_completed(tid, "t", 1.0, 1.0)
        assert m.accuracy_report().average_pct is None   # Table 2 "NA"

    def test_parent_child_subtraction(self):
        m = TaskMonitor(min_samples=1)
        # establish α = 1 s/unit
        m.on_task_ready(0, "p", 4.0)
        m.on_task_execute(0, "p", 4.0)
        m.on_task_completed(0, "p", 4.0, 4.0)
        # parent predicted 4 s; child runs 1.5 s
        m.on_task_ready(1, "p", 4.0)
        assert math.isclose(m._outstanding[1], 4.0)
        m.on_task_ready(2, "c", 1.0)
        m.on_task_execute(2, "c", 1.0)
        m.on_task_completed(2, "c", 1.0, 1.5, parent_id=1)
        assert math.isclose(m._outstanding[1], 2.5)

    @given(st.lists(st.tuples(st.floats(0.1, 100.0), st.floats(0.01, 10.0)),
                    min_size=1, max_size=50))
    @settings(max_examples=100, deadline=None)
    def test_snapshot_never_negative(self, tasks):
        """Property: live cost/instances never go negative through any
        ready→execute→complete sequence."""
        m = TaskMonitor()
        for tid, (cost, elapsed) in enumerate(tasks):
            m.on_task_ready(tid, "t", cost)
            m.on_task_execute(tid, "t", cost)
            m.on_task_completed(tid, "t", cost, elapsed)
            for _n, w, _a, mm, _r in m.workload_snapshot():
                assert w >= -1e-9 and mm >= 0

"""Elastic controller + straggler mitigation + fault-tolerant training."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.core.conditions import (ConditionTimeline, core_fail,
                                   core_recover, straggler)
from repro.train.elastic import ElasticController, ReplicaSet
from repro.train.straggler import StragglerMonitor


class TestReplicaSet:
    @given(st.integers(1, 64), st.integers(1, 1024))
    @settings(max_examples=100, deadline=None)
    def test_shards_conserve_batch(self, n, batch):
        rs = ReplicaSet(list(range(n)), batch)
        shards = rs.shards()
        assert sum(shards.values()) == batch
        assert max(shards.values()) - min(shards.values()) <= 1


class TestElasticController:
    def _seed(self, c: ElasticController, step_time=0.1, n=6):
        for i in range(n):
            c.on_batches_queued(1, tokens_per_batch=1000.0)
            c.on_step_done(c._task_seq, 1000.0, step_time)

    def test_failure_shrinks_and_rebalances(self):
        c = ElasticController(max_replicas=8, global_batch=256)
        new = c.fail_replica(3, step=10)
        assert 3 not in new.replicas and len(new.replicas) == 7
        assert sum(new.shards().values()) == 256

    def test_prediction_shrinks_when_idle(self):
        c = ElasticController(max_replicas=8, global_batch=64,
                              rate_s=0.1)
        self._seed(c)
        # no queued work ⇒ Δ collapses to 1
        rs = c.resize_to_prediction(step=1)
        assert len(rs.replicas) == 1

    def test_prediction_grows_with_backlog(self):
        c = ElasticController(max_replicas=8, global_batch=64,
                              rate_s=0.1)
        self._seed(c, step_time=0.1)
        # 8 batches × 0.1 s backlog over a 0.1 s window ⇒ want 8 replicas
        c.on_batches_queued(8, tokens_per_batch=1000.0)
        c.set = ReplicaSet([0], 64)
        rs = c.resize_to_prediction(step=2)
        assert len(rs.replicas) == 8

    def test_failed_never_readmitted(self):
        c = ElasticController(max_replicas=4, global_batch=32)
        c.fail_replica(2, step=0)
        self._seed(c)
        c.on_batches_queued(16, tokens_per_batch=1000.0)
        rs = c.resize_to_prediction(step=1)
        assert 2 not in rs.replicas
        assert len(rs.replicas) <= 3

    def test_busy_policy_keeps_everything(self):
        c = ElasticController(max_replicas=6, global_batch=32,
                              policy="busy")
        self._seed(c)
        assert len(c.resize_to_prediction(0).replicas) == 6


class TestFaultTolerantTraining:
    """CORE_FAIL mid-run → checkpoint-restore → completion with the
    surviving replicas (the dormant straggler/checkpoint hooks wired
    into the controller)."""

    def _run(self, c: ElasticController, timeline: ConditionTimeline,
             steps: int = 10, every: int = 2):
        state = {"w": np.zeros(4, dtype=np.float64)}
        fired = {p.time: p for p in timeline}
        step = 0
        while step < steps:
            state = {"w": state["w"] + 1.0}
            step += 1
            c.on_batches_queued(1, tokens_per_batch=1000.0)
            c.on_step_done(c._task_seq, 1000.0, 0.1,
                           replica=c.set.replicas[0])
            c.maybe_checkpoint(step, state, every=every)
            p = fired.pop(float(step), None)
            if p is not None:
                _, state, step = c.apply_perturbation(p, step, state)
        return state, step

    def test_core_fail_restores_and_completes(self, tmp_path):
        c = ElasticController(max_replicas=4, global_batch=32,
                              checkpoint=CheckpointManager(tmp_path))
        tl = ConditionTimeline([core_fail(5.0, 2)])
        state, step = self._run(c, tl, steps=10, every=2)
        # rolled back from the failure at step 5 to the step-4 save...
        assert c.restores == [(5, 4)]
        # ...and completed the full run on the survivors
        assert step == 10
        assert float(state["w"][0]) == 10.0
        assert 2 not in c.set.replicas
        assert len(c.set.replicas) == 3
        assert sum(c.set.shards().values()) == 32

    def test_core_fail_without_checkpoint_keeps_live_state(self, tmp_path):
        c = ElasticController(max_replicas=4, global_batch=32)
        tl = ConditionTimeline([core_fail(5.0, 1)])
        state, step = self._run(c, tl, steps=8)
        assert c.restores == []          # nothing to roll back to
        assert float(state["w"][0]) == 8.0
        assert 1 not in c.set.replicas

    def test_recover_rejoins_candidate_pool(self, tmp_path):
        c = ElasticController(max_replicas=4, global_batch=32, rate_s=0.1,
                              checkpoint=CheckpointManager(tmp_path))
        tl = ConditionTimeline([core_fail(3.0, 2), core_recover(6.0, 2)])
        self._run(c, tl, steps=8)
        assert 2 not in c.failed          # recovered
        # backlog-driven growth may now re-admit it
        c.on_batches_queued(16, tokens_per_batch=1000.0)
        rs = c.resize_to_prediction(step=9)
        assert len(rs.replicas) == 4

    def test_straggler_perturbation_drains_replica(self):
        c = ElasticController(max_replicas=4, global_batch=32,
                              straggler=StragglerMonitor())
        p = straggler(2.0, 3, 4.0)
        rs, _, _ = c.apply_perturbation(p, step=2, state=None)
        assert 3 not in rs.replicas
        assert 3 in c.straggler.drained
        # not a permanent failure: grows may re-admit after cooldown
        assert 3 not in c.failed

    def test_sweep_drains_observed_straggler(self):
        c = ElasticController(max_replicas=8, global_batch=64,
                              straggler=StragglerMonitor(threshold=1.5))
        for _ in range(6):
            for r in range(7):
                c.straggler.observe(r, 0.10)
            c.straggler.observe(7, 0.40)
        rs = c.sweep_stragglers(step=6)
        assert 7 not in rs.replicas
        assert len(rs.replicas) == 7
        # drained replicas are skipped by prediction-driven growth
        c.on_batches_queued(16, tokens_per_batch=1000.0)
        for i in range(6):
            c.on_step_done(c._task_seq - i, 1000.0, 0.1)
        rs = c.resize_to_prediction(step=7)
        assert 7 not in rs.replicas


class TestStraggler:
    def test_detects_slow_worker(self):
        m = StragglerMonitor(threshold=1.5)
        for _ in range(6):
            for w in range(7):
                m.observe(w, 0.10)
            m.observe(7, 0.30)
        assert m.sweep() == {7}
        assert m.is_straggler(7)
        assert not m.is_straggler(0)

    def test_cooldown_readmission(self):
        m = StragglerMonitor(threshold=1.5, cooldown=3)
        for _ in range(6):
            for w in range(3):
                m.observe(w, 0.10)
            m.observe(3, 0.50)
        assert m.sweep() == {3}
        # the worker recovers; EMA drifts back under the threshold
        for _ in range(30):
            for w in range(3):
                m.observe(w, 0.10)
            m.observe(3, 0.10)
        assert 3 not in m.drained

    def test_no_flags_with_uniform_fleet(self):
        m = StragglerMonitor()
        for _ in range(10):
            for w in range(16):
                m.observe(w, 0.1)
        assert m.sweep() == set()

"""Dynamic machine conditions: timelines, clamps, broker invariants,
and perturbed replay round trips.

Covers the conditions subsystem end to end:

* ``Perturbation`` / ``ConditionTimeline`` construction, serialization,
  seeded scenario determinism, and ``neutralized()`` semantics;
* the ``PowerModel.power`` / ``MachineModel.service_time`` frequency
  clamp contracts (documented in their docstrings);
* ``EnergyMeter`` lazy power-cap violation accounting;
* ``ResourceBroker`` fail/recover invariants, deterministically and
  under random interleavings of the sharing verbs (no core
  simultaneously lent and failed; pool counts conserve);
* perturbed sim→sim trace replays: the PERTURBATION events round-trip
  the timeline byte-exactly and replay-of-replay is a fixed point for
  every policy on both a homogeneous and a heterogeneous machine;
* the empty timeline as the degenerate case: byte-identical traces and
  bit-identical reports vs. no conditions at all.
"""

import itertools
import json
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import EventBus, EventKind, GovernorSpec
from repro.core.conditions import (ConditionTimeline, MachineConditions,
                                   Perturbation, PerturbationKind,
                                   core_fail, core_recover, power_cap,
                                   straggler, thermal_throttle)
from repro.core.energy import CoreState, EnergyMeter, PowerModel
from repro.core.sharing import ResourceBroker
from repro.runtime import task as task_mod
from repro.runtime import (DVFS2, HYBRID_PE, MN4, SimCluster, SimExecutor,
                           SimJobSpec, Task, TaskGraph)
from repro.trace import TraceRecorder, TraceReplayer


def wave_graph(seed=0, n_waves=6, width=8):
    """Waves of parallel tasks separated by barriers (test_trace idiom)."""
    rng = random.Random(seed)
    g = TaskGraph()
    prev = None
    for _ in range(n_waves):
        wave = [Task("wave", cost=1.0,
                     service_time=rng.uniform(5e-5, 2e-4))
                for _ in range(width)]
        for t in wave:
            if prev is not None:
                t.depends_on(prev)
            g.add(t)
        bar = Task("barrier", cost=0.1, service_time=1e-5)
        for t in wave:
            bar.depends_on(t)
        g.add(bar)
        prev = bar
    return g


def perturbed_timeline():
    """One of everything, timed to land mid-run for wave_graph()."""
    return ConditionTimeline([
        power_cap(0.0, 20.0),
        core_fail(0.0005, 2),
        straggler(0.001, 5, 4.0),
        thermal_throttle(0.0015, "P", 0.6),
        core_recover(0.002, 2),
    ])


def trace_bytes(rec: TraceRecorder) -> str:
    return "\n".join(json.dumps(e.to_dict()) for e in rec.merged_events())


# ---------------------------------------------------------------------------
# Perturbation / ConditionTimeline
# ---------------------------------------------------------------------------

class TestTimeline:
    def test_serialization_round_trip(self):
        tl = perturbed_timeline()
        back = ConditionTimeline.from_dicts(tl.to_dicts())
        assert back.to_dicts() == tl.to_dicts()
        assert list(back) == list(tl)

    def test_sorted_by_time_then_insertion(self):
        a, b = core_fail(1.0, 0), core_fail(1.0, 1)
        tl = ConditionTimeline([straggler(2.0, 3, 2.0), b, a])
        assert [p.core for p in tl] == [1, 0, 3]

    def test_negative_time_rejected(self):
        with pytest.raises(ValueError):
            ConditionTimeline([core_fail(-0.1, 0)])

    def test_straggler_slowdown_validated(self):
        with pytest.raises(ValueError):
            straggler(0.0, 0, 0.5)

    def test_empty_timeline_is_falsy(self):
        assert not ConditionTimeline()
        assert perturbed_timeline()

    def test_neutralized_disarms_speed_keeps_structure(self):
        tl = perturbed_timeline().neutralized()
        by_kind = {p.kind: p for p in tl}
        # speed-changing perturbations are disarmed...
        assert by_kind[PerturbationKind.STRAGGLER].slowdown == 1.0
        assert by_kind[PerturbationKind.THERMAL_THROTTLE].freq == 1.0
        # ...but the STRAGGLER keeps its suspect marker
        mc = MachineConditions()
        mc.apply(by_kind[PerturbationKind.STRAGGLER])
        assert mc.is_suspect(5)
        assert mc.slowdown_of(5) == 1.0
        # structural perturbations survive verbatim
        assert by_kind[PerturbationKind.POWER_CAP].watts == 20.0
        assert by_kind[PerturbationKind.CORE_FAIL].core == 2
        # idempotent: the replay-of-replay fixed point depends on this
        assert tl.neutralized().to_dicts() == tl.to_dicts()

    def test_random_faults_seeded_deterministic(self):
        kw = dict(n_cores=16, horizon=1.0, n_faults=4, mttr=0.1)
        a = ConditionTimeline.random_faults(seed=7, **kw)
        b = ConditionTimeline.random_faults(seed=7, **kw)
        c = ConditionTimeline.random_faults(seed=8, **kw)
        assert a.to_dicts() == b.to_dicts()
        assert a.to_dicts() != c.to_dicts()
        fails = [p for p in a if p.kind is PerturbationKind.CORE_FAIL]
        assert len(fails) == 4
        assert len({p.core for p in fails}) == 4     # distinct cores
        for p in a:
            assert 0.0 <= p.time < 1.0
        # every recover follows its core's failure
        fail_at = {p.core: p.time for p in fails}
        for p in a:
            if p.kind is PerturbationKind.CORE_RECOVER:
                assert p.time >= fail_at[p.core]

    def test_random_stragglers_in_range(self):
        tl = ConditionTimeline.random_stragglers(
            n_cores=8, horizon=2.0, n_stragglers=3,
            slowdown_range=(2.0, 4.0), seed=3)
        assert len(tl) == 3
        for p in tl:
            assert 2.0 <= p.slowdown <= 4.0


class TestMachineConditions:
    def test_fail_recover(self):
        mc = MachineConditions()
        mc.apply(core_fail(0.0, 3))
        assert mc.is_failed(3) and mc.failed_cores() == [3]
        mc.apply(core_recover(1.0, 3))
        assert not mc.is_failed(3) and not mc.any_active

    def test_thermal_cap_set_and_lift(self):
        mc = MachineConditions()
        mc.apply(thermal_throttle(0.0, "P", 0.6))
        assert mc.thermal_cap("P") == 0.6
        assert mc.thermal_cap("E") == 1.0
        mc.apply(thermal_throttle(1.0, "P", None))
        assert mc.thermal_cap("P") == 1.0
        assert not mc.any_active

    def test_straggler_cured_only_by_none(self):
        mc = MachineConditions()
        mc.apply(straggler(0.0, 4, 3.0))
        assert mc.slowdown_of(4) == 3.0 and mc.is_suspect(4)
        # slowdown 1.0 = disarmed but still suspect (replay semantics)
        mc.apply(Perturbation(1.0, PerturbationKind.STRAGGLER, core=4,
                              slowdown=1.0))
        assert mc.slowdown_of(4) == 1.0 and mc.is_suspect(4)
        mc.apply(Perturbation(2.0, PerturbationKind.STRAGGLER, core=4))
        assert not mc.is_suspect(4) and not mc.any_active

    def test_power_cap_set_and_lift(self):
        mc = MachineConditions()
        mc.apply(power_cap(0.0, 25.0))
        assert mc.power_cap_w == 25.0 and mc.any_active
        mc.apply(power_cap(1.0, None))
        assert mc.power_cap_w is None and not mc.any_active

    @given(st.lists(st.tuples(st.booleans(), st.integers(0, 7)),
                    max_size=60))
    @settings(max_examples=100, deadline=None)
    def test_failed_set_tracks_reference(self, ops):
        mc = MachineConditions()
        ref: set[int] = set()
        for fail, core in ops:
            if fail:
                mc.apply(core_fail(0.0, core))
                ref.add(core)
            else:
                mc.apply(core_recover(0.0, core))
                ref.discard(core)
            assert set(mc.failed_cores()) == ref
            assert mc.is_failed(core) == (core in ref)


# ---------------------------------------------------------------------------
# Frequency clamp contracts (PowerModel.power / MachineModel.service_time)
# ---------------------------------------------------------------------------

class TestPowerModelClamp:
    def test_above_band_clamps_to_base(self):
        pm = PowerModel()
        assert pm.power(CoreState.ACTIVE, 1.5) == pm.active
        assert pm.power(CoreState.SPIN, 7.0) == pm.spin

    def test_below_band_clamps_to_idle_floor(self):
        pm = PowerModel()
        # freq < 0 clamps to 0: the dynamic term vanishes, never negative
        assert pm.power(CoreState.ACTIVE, -2.0) == pm.idle
        assert pm.power(CoreState.ACTIVE, 0.0) == pm.idle

    def test_in_band_bit_identical_cubic(self):
        pm = PowerModel(active=0.8, idle=0.05)
        f = 0.73
        assert pm.power(CoreState.ACTIVE, f) == \
            pm.idle + (pm.active - pm.idle) * f ** 3
        assert pm.power(CoreState.ACTIVE, 1.0) == pm.active

    def test_static_states_ignore_frequency(self):
        pm = PowerModel()
        for f in (-1.0, 0.4, 1.0, 2.0):
            assert pm.power(CoreState.IDLE, f) == pm.idle
            assert pm.power(CoreState.OFF, f) == pm.off


class TestServiceTimeClamp:
    def test_above_band_clamps_to_max_freq(self):
        assert MN4.service_time(1.0, 0, freq=2.0) == \
            MN4.service_time(1.0, 0, freq=1.0)
        assert DVFS2.service_time(1.0, 0, freq=1.5) == \
            DVFS2.service_time(1.0, 0, freq=1.0)

    def test_nonpositive_clamps_to_lowest_step(self):
        # DVFS2 sockets publish steps (0.75, 0.875, 1.0)
        assert DVFS2.service_time(1.0, 0, freq=0.0) == \
            DVFS2.service_time(1.0, 0, freq=0.75)
        assert DVFS2.service_time(1.0, 0, freq=-1.0) == \
            DVFS2.service_time(1.0, 0, freq=0.75)
        # homogeneous machines fall back to their single full step —
        # a frequency of zero must never stall the task forever
        assert MN4.service_time(1.0, 0, freq=0.0) == \
            MN4.service_time(1.0, 0, freq=1.0)

    def test_in_band_honored_bit_identically(self):
        # 0.8 sits between DVFS2's published steps — thermal throttling
        # legitimately pins a core below/between its nominal steps
        assert DVFS2.service_time(1.0, 0, freq=0.8) == \
            1.0 / (DVFS2.speed_of(0) * 0.8)
        # heterogeneous: E-core speed scales the same clamped band
        e_core = 10   # HYBRID_PE cores 8..23 are E-cores
        assert HYBRID_PE.service_time(1.0, e_core, freq=0.5) == \
            1.0 / (HYBRID_PE.speed_of(e_core) * 0.5)


# ---------------------------------------------------------------------------
# EnergyMeter power-cap violation accounting
# ---------------------------------------------------------------------------

class TestCapViolationAccounting:
    def test_lazy_until_first_cap(self):
        m = EnergyMeter(4)
        m.set_state(0, CoreState.ACTIVE, 1.0)
        m.finish(2.0)
        assert m.power_cap_w is None
        assert m.cap_violation_s == 0.0

    def test_violation_seconds_accumulate(self):
        m = EnergyMeter(2)                    # both cores SPIN at 1.0 W
        m.set_power_cap(0.0, 1.5)
        assert m.watts == pytest.approx(2.0)  # 2.0 W > 1.5 W cap
        m.set_state(0, CoreState.IDLE, 1.0)   # 1.1 W <= cap from t=1
        assert m.cap_violation_s == pytest.approx(1.0)
        m.finish(3.0)
        assert m.watts == pytest.approx(1.1)
        assert m.cap_violation_s == pytest.approx(1.0)

    def test_lifting_cap_stops_violation(self):
        m = EnergyMeter(2)
        m.set_power_cap(0.0, 1.5)
        m.set_power_cap(1.0, None)            # lift: 1 violating second
        m.finish(5.0)
        assert m.cap_violation_s == pytest.approx(1.0)


class TestMachineWideCap:
    """SimCluster integrates the *summed* draw of all live jobs against
    the cap — per-job meters can only judge their own slice, so two
    individually compliant tenants can still blow the machine budget."""

    def _cluster(self, cap_w, jobs):
        tl = ConditionTimeline([power_cap(0.0, cap_w)])
        cl = SimCluster(MN4, conditions=tl)
        for name, seed, cpus in jobs:
            cl.add_job(SimJobSpec(name=name, graph=wave_graph(seed=seed),
                                  policy="busy", cpus=cpus))
        return cl, cl.run()

    def test_single_tenant_matches_meter(self):
        # with one job owning the whole machine, the machine-wide
        # integral and the job's own meter see the same draw
        cl, reps = self._cluster(20.0, [("app", 0, list(range(48)))])
        assert cl.machine_cap_violation_s > 0.0
        assert cl.machine_cap_violation_s == pytest.approx(
            reps["app"].cap_violation_s, rel=1e-6)

    def test_two_compliant_tenants_blow_the_budget(self):
        # 24 spinning cores each = 24 W per meter, under the 30 W cap —
        # but 48 W together: only the machine-wide integral notices
        cl, reps = self._cluster(
            30.0, [("a", 0, list(range(24))),
                   ("b", 1, list(range(24, 48)))])
        for rep in reps.values():
            assert rep.cap_violation_s == 0.0
        first_done = min(r.makespan for r in reps.values())
        assert cl.machine_cap_violation_s == pytest.approx(
            first_done, rel=1e-6)

    def test_finished_tenants_stop_drawing(self):
        # after the shorter job completes, the survivor's 24 W sits
        # under the cap — the finished job's frozen meter must not
        # keep counting phantom watts against the machine
        cl, reps = self._cluster(
            25.0, [("a", 0, list(range(24))),
                   ("b", 1, list(range(24, 48)))])
        first_done = min(r.makespan for r in reps.values())
        assert cl.machine_cap_violation_s == pytest.approx(
            first_done, rel=1e-6)
        assert cl.machine_cap_violation_s < max(
            r.makespan for r in reps.values())


# ---------------------------------------------------------------------------
# ResourceBroker fail/recover invariants
# ---------------------------------------------------------------------------

def _two_job_broker() -> ResourceBroker:
    b = ResourceBroker()
    b.register_job("A", [0, 1, 2, 3])
    b.register_job("B", [4, 5, 6, 7])
    return b


def _owner_of(cpu: int) -> str:
    return "A" if cpu < 4 else "B"


def _check_invariants(b: ResourceBroker) -> None:
    pooled = [c for c in range(8) if b.holder(c) == ""]
    # pool count conserves: the pool is exactly the holder-less CPUs
    assert b.pool_size() == len(pooled)
    for cpu in range(8):
        if b.is_failed(cpu):
            # a failed core is parked with its owner: never in the
            # pool, never lent, never held by a borrower
            assert b.holder(cpu) == _owner_of(cpu)
    assert not any(b.is_failed(c) for c in pooled)


class TestBrokerFaults:
    def test_fail_pulls_from_pool_and_refuses_lend(self):
        b = _two_job_broker()
        b.lend("A", 0)
        assert b.pool_size() == 1
        b.fail_core(0)
        assert b.pool_size() == 0
        assert b.holder(0) == "A"
        # dead silicon cannot be lent or granted
        b.lend("A", 0)
        assert b.pool_size() == 0
        assert b.acquire("B", 4) == []
        _check_invariants(b)

    def test_fail_borrowed_core_reports_holder(self):
        b = _two_job_broker()
        b.lend("A", 1)
        assert b.acquire("B", 1) == [1]
        assert b.fail_core(1) == "B"       # B must evict its worker
        assert b.holder(1) == "A"
        _check_invariants(b)

    def test_recover_rejoins_owner_directly(self):
        b = _two_job_broker()
        b.fail_core(2)
        assert b.recover_core(2) == "A"
        assert not b.is_failed(2)
        assert b.holder(2) == "A"
        assert b.pool_size() == 0          # never resurfaces via the pool
        b.lend("A", 2)                     # lendable again after recovery
        assert b.pool_size() == 1
        _check_invariants(b)

    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 7)),
                    max_size=80))
    @settings(max_examples=200, deadline=None)
    def test_invariants_under_random_interleavings(self, ops):
        b = _two_job_broker()
        for op, cpu in ops:
            owner = _owner_of(cpu)
            other = "B" if owner == "A" else "A"
            if op == 0:                       # current holder lends
                h = b.holder(cpu)
                if h:
                    b.lend(h, cpu)
            elif op == 1:                     # the other job borrows
                for got in b.acquire(other, 1):
                    assert not b.is_failed(got)
            elif op == 2:
                b.reclaim(owner)
            elif op == 3:
                if not b.is_failed(cpu):
                    b.fail_core(cpu)
            elif op == 4:
                if b.is_failed(cpu):
                    b.recover_core(cpu)
            else:                             # borrower hands it back
                h = b.holder(cpu)
                if h and h != owner and not b.is_failed(cpu):
                    b.return_cpu(h, cpu)
            _check_invariants(b)


# ---------------------------------------------------------------------------
# Perturbed sim runs: behaviour
# ---------------------------------------------------------------------------

class TestPerturbedRuns:
    def test_core_fail_requeues_and_completes(self):
        g = wave_graph()
        n_tasks = len(g.tasks)
        spec = GovernorSpec(resources=8, policy="busy", monitoring=True)
        ex = SimExecutor(MN4, spec=spec,
                         conditions=ConditionTimeline(
                             [core_fail(0.0005, 2)]))
        r = ex.run(g)
        # the in-flight task on core 2 was re-queued, nothing was lost
        assert r.tasks_completed == n_tasks

    def test_straggler_dilates_makespan(self):
        spec = GovernorSpec(resources=8, policy="busy", monitoring=True)
        base = SimExecutor(MN4, spec=spec).run(wave_graph()).makespan
        slow = SimExecutor(
            MN4, spec=spec,
            conditions=ConditionTimeline([straggler(0.0, 0, 8.0)]),
        ).run(wave_graph()).makespan
        assert slow > base

    def test_power_cap_violation_surfaces_in_report(self):
        spec = GovernorSpec(resources=8, policy="busy", monitoring=True)
        r = SimExecutor(
            MN4, spec=spec,
            conditions=ConditionTimeline([power_cap(0.0, 1.0)]),
        ).run(wave_graph())
        # busy keeps 8 cores hot against a 1 W budget: violation time
        # is essentially the whole run
        assert r.cap_violation_s > 0.0
        assert r.cap_violation_s == pytest.approx(r.makespan, rel=0.2)

    def test_thermal_throttle_slows_typed_machine(self):
        spec = GovernorSpec(resources=24, policy="busy", monitoring=True,
                            topology=HYBRID_PE.topology())
        base = SimExecutor(HYBRID_PE, spec=spec) \
            .run(wave_graph(width=24)).makespan
        hot = SimExecutor(
            HYBRID_PE, spec=spec,
            conditions=ConditionTimeline(
                [thermal_throttle(0.0, "P", 0.5)]),
        ).run(wave_graph(width=24)).makespan
        assert hot > base


# ---------------------------------------------------------------------------
# Perturbed trace replay round trips
# ---------------------------------------------------------------------------

MACHINES = [(MN4, 8, "mn4"), (HYBRID_PE, 24, "hybrid")]
POLICIES = ["busy", "idle", "hybrid", "prediction", "hetero-prediction"]


def _spec(machine, n, policy):
    return GovernorSpec(
        resources=n, policy=policy, monitoring=True,
        topology=machine.topology() if machine.core_types else None)


def _record_run(machine, n, policy, conditions):
    task_mod._ids = itertools.count()
    ex = SimExecutor(machine, spec=_spec(machine, n, policy),
                     conditions=conditions)
    rec = TraceRecorder(bus=ex.bus)
    report = ex.run(wave_graph())
    return rec, report


def _record_replay(rec, spec):
    task_mod._ids = itertools.count()
    bus = EventBus()
    rec2 = TraceRecorder(bus=bus)
    report = TraceReplayer(rec).replay(spec, bus=bus)
    return rec2, report


class TestPerturbedReplay:
    @pytest.mark.parametrize("policy", POLICIES)
    @pytest.mark.parametrize("machine,n",
                             [(m, n) for m, n, _ in MACHINES],
                             ids=[i for _, _, i in MACHINES])
    def test_replay_of_replay_is_byte_exact(self, machine, n, policy):
        spec = _spec(machine, n, policy)
        rec1, r1 = _record_run(machine, n, policy, perturbed_timeline())

        # the recorded PERTURBATION events reconstruct the timeline —
        # exactly the prefix that fired before the run completed
        tl = TraceReplayer(rec1).conditions()
        assert tl is not None
        scheduled = perturbed_timeline().to_dicts()
        assert len(tl) >= 3
        assert tl.to_dicts() == scheduled[:len(tl)]

        # first replay: neutral machine, neutralized conditions
        rec2, r2 = _record_replay(rec1, spec)
        assert r2.tasks_completed == r1.tasks_completed
        # replays re-record the neutralized form of the recorded prefix
        tl2 = TraceReplayer(rec2).conditions()
        assert tl2 is not None
        assert tl2.to_dicts() == tl.neutralized().to_dicts()[:len(tl2)]

        # replay-of-replay is a fixed point: byte-identical trace,
        # bit-identical report
        rec3, r3 = _record_replay(rec2, spec)
        assert trace_bytes(rec3) == trace_bytes(rec2)
        assert repr(r3) == repr(r2)

    def test_unperturbed_trace_has_no_conditions(self):
        rec, _ = _record_run(MN4, 8, "busy", None)
        assert TraceReplayer(rec).conditions() is None


# ---------------------------------------------------------------------------
# Empty timeline = degenerate case
# ---------------------------------------------------------------------------

class TestEmptyTimelineParity:
    @pytest.mark.parametrize("policy", ["busy", "prediction"])
    def test_empty_timeline_byte_identical_to_none(self, policy):
        rec_none, r_none = _record_run(MN4, 8, policy, None)
        rec_empty, r_empty = _record_run(MN4, 8, policy,
                                         ConditionTimeline())
        assert trace_bytes(rec_empty) == trace_bytes(rec_none)
        assert repr(r_empty) == repr(r_none)
        assert r_empty.cap_violation_s == 0.0

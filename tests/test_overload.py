"""SLO-aware overload protection: admission, retries, hedging, breakers,
brownout, and the discrete-event serving frontend.

Everything here runs in virtual time — no jax, no wall clock — except
the ServingEngine satellite tests at the bottom, which build the real
engine (smoke config) but never decode.
"""

import json

import pytest

from hypothesis import given, settings, strategies as st
from repro.core.conditions import (ConditionTimeline, core_fail,
                                   core_recover, power_cap, straggler,
                                   thermal_throttle)
from repro.core.events import EventBus, EventKind
from repro.runtime.machine import HYBRID_PE, MachineModel
from repro.serving import (AdmissionController, CircuitBreaker,
                           SLOClass, ServingModel, SimRequest, SimServing,
                           build_requests, cap_allowance)
from repro.serving.slo import BATCH, INTERACTIVE, STANDARD
from repro.trace import TraceRecorder
from repro.workloads.arrivals import PoissonArrivals

TINY = MachineModel(name="tiny", n_cores=4)


def _model(machine=TINY, **kw):
    kw.setdefault("slots_per_replica", 2)
    return ServingModel(machine=machine, **kw)


# ---------------------------------------------------------------------------
# Admission controller
# ---------------------------------------------------------------------------


def test_admission_queue_bound():
    adm = AdmissionController(max_queue_depth=3)
    assert adm.shed_reason(now=0.0, queue_depth=2, slo=None,
                           submitted_at=0.0) is None
    assert adm.shed_reason(now=0.0, queue_depth=3, slo=None,
                           submitted_at=0.0) == "queue"


def test_admission_deadline_infeasibility():
    adm = AdmissionController()
    slo = SLOClass("t", deadline_s=1.0)
    # eta = now + wait + service vs submitted_at + deadline * slack
    assert adm.shed_reason(now=0.0, queue_depth=0, slo=slo,
                           submitted_at=0.0, est_wait_s=0.3,
                           est_service_s=0.3) is None
    assert adm.shed_reason(now=0.0, queue_depth=0, slo=slo,
                           submitted_at=0.0, est_wait_s=0.8,
                           est_service_s=0.3) == "deadline"
    # slack > 1 tolerates the same overshoot
    loose = AdmissionController(slack=1.5)
    assert loose.shed_reason(now=0.0, queue_depth=0, slo=slo,
                             submitted_at=0.0, est_wait_s=0.8,
                             est_service_s=0.3) is None
    # no SLO / no deadline: only the queue bound can shed
    assert adm.shed_reason(now=0.0, queue_depth=10 ** 6, slo=None,
                           submitted_at=0.0, est_wait_s=1e9) is None


def test_admission_validates():
    with pytest.raises(ValueError):
        AdmissionController(max_queue_depth=0)
    with pytest.raises(ValueError):
        AdmissionController(slack=0.0)


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_closed_to_open_to_half_open_to_closed():
    brk = CircuitBreaker(failure_threshold=2, reset_after_s=1.0,
                         probe_successes=2)
    assert brk.state(0.0) == CircuitBreaker.CLOSED
    brk.record_failure(0.1)
    assert brk.state(0.1) == CircuitBreaker.CLOSED
    brk.record_failure(0.2)
    assert brk.state(0.2) == CircuitBreaker.OPEN
    assert not brk.allow(0.5)
    # cooldown elapses: asking advances OPEN -> HALF_OPEN
    assert brk.state(1.2) == CircuitBreaker.HALF_OPEN
    assert brk.allow(1.2)
    brk.record_success(1.3)
    assert brk.state(1.3) == CircuitBreaker.HALF_OPEN  # 1 of 2 probes
    brk.record_success(1.4)
    assert brk.state(1.4) == CircuitBreaker.CLOSED


def test_breaker_half_open_failure_reopens():
    brk = CircuitBreaker(failure_threshold=1, reset_after_s=1.0)
    brk.record_failure(0.0)
    assert brk.state(1.5) == CircuitBreaker.HALF_OPEN
    brk.record_failure(1.6)
    assert brk.state(1.6) == CircuitBreaker.OPEN
    # the reopen restarts the cooldown from the failure instant
    assert brk.state(2.5) == CircuitBreaker.OPEN
    assert brk.state(2.7) == CircuitBreaker.HALF_OPEN


def test_breaker_success_resets_failure_streak():
    brk = CircuitBreaker(failure_threshold=2)
    brk.record_failure(0.0)
    brk.record_success(0.1)   # streak broken
    brk.record_failure(0.2)
    assert brk.state(0.2) == CircuitBreaker.CLOSED


def test_breaker_force_open():
    brk = CircuitBreaker(failure_threshold=100, reset_after_s=2.0)
    brk.force_open(5.0)
    assert brk.state(6.9) == CircuitBreaker.OPEN
    assert brk.state(7.0) == CircuitBreaker.HALF_OPEN


# ---------------------------------------------------------------------------
# Power-cap allowance
# ---------------------------------------------------------------------------


def test_cap_allowance_homogeneous():
    # 48 replicas at (1.0 active, 0.1 idle) under a 30 W cap:
    # budget = 30 - 4.8 = 25.2, step 0.9 -> exactly 28 (equality holds)
    draws = [(1.0, 0.1)] * 48
    assert cap_allowance(30.0, draws) == 28
    assert cap_allowance(1000.0, draws) == 48
    assert cap_allowance(0.0, draws) == 0


def test_cap_allowance_ordered_greedy():
    # fastest-first ordering is the caller's: P cores cost 0.9/step,
    # E cores 0.35/step — the allowance charges them in list order
    draws = [(1.0, 0.1)] * 2 + [(0.4, 0.05)] * 2
    # budget = cap - 0.3; two P steps = 1.8, each E step 0.35
    assert cap_allowance(2.1, draws) == 2
    assert cap_allowance(2.45, draws) == 3
    assert cap_allowance(2.8, draws) == 4


# ---------------------------------------------------------------------------
# SLO classes: backoff + serialization
# ---------------------------------------------------------------------------


def test_backoff_seeded_and_order_independent():
    slo = SLOClass("t", backoff_base_s=0.1, backoff_jitter=0.25)
    a = slo.backoff(1, seed=7, request_id=42)
    b = slo.backoff(2, seed=7, request_id=42)
    # keyed on (seed, request_id, attempt): replaying in any order or
    # interleaving other requests changes nothing
    slo.backoff(1, seed=7, request_id=99)
    assert slo.backoff(1, seed=7, request_id=42) == a
    assert slo.backoff(2, seed=7, request_id=42) == b
    # exponential base with bounded jitter
    assert 0.075 <= a <= 0.125
    assert 0.15 <= b <= 0.25
    # different key -> (almost surely) different draw
    assert slo.backoff(1, seed=8, request_id=42) != a


def test_backoff_no_jitter_is_exact():
    slo = SLOClass("t", backoff_base_s=0.2, backoff_jitter=0.0)
    assert slo.backoff(1) == 0.2
    assert slo.backoff(3) == 0.8
    with pytest.raises(ValueError):
        slo.backoff(0)


def test_slo_roundtrip():
    for slo in (INTERACTIVE, STANDARD, BATCH,
                SLOClass("x", deadline_s=2.0, priority=5, timeout_s=0.5,
                         retry_budget=3, backoff_base_s=0.01,
                         backoff_jitter=0.0, hedge_after_s=0.3,
                         best_effort=True)):
        assert SLOClass.from_dict(slo.to_dict()) == slo


# ---------------------------------------------------------------------------
# SimServing: targeted scenarios
# ---------------------------------------------------------------------------


def test_sim_completes_unloaded():
    reqs = [SimRequest(rid=i, release=0.1 * i, prompt=100, new=32,
                       slo=STANDARD) for i in range(20)]
    sim = SimServing(_model(), reqs, policy="busy").run()
    rep = sim.report("t")
    assert rep.serving["completed"] == 20
    assert rep.serving["shed"] == 0 and rep.serving["timed_out"] == 0
    assert rep.serving["attainment"] == 1.0
    assert all(r.outcome == "completed" for r in sim.requests)


def test_timeout_retry_then_give_up():
    # service (100/4000 + 80/160 = 0.525 s) >> timeout 0.1 s: every
    # attempt times out; one retry is granted, then the request fails
    slo = SLOClass("tight", deadline_s=30.0, timeout_s=0.1,
                   retry_budget=1, backoff_base_s=0.05)
    reqs = [SimRequest(rid=0, release=0.0, prompt=100, new=80, slo=slo)]
    bus = EventBus()
    rec = TraceRecorder(bus)
    sim = SimServing(_model(), reqs, policy="busy", bus=bus, seed=3).run()
    req = sim.requests[0]
    assert req.outcome == "timed_out"
    assert req.tries == 2
    rep = sim.report("t")
    assert rep.serving["retries"] == 1
    assert rep.serving["timed_out"] == 1
    assert rep.serving["shed_by_reason"] == {"timeout": 1}
    # the RETRY event carries the seeded backoff the sim actually used
    retry_evs = [e for e in rec.events if e.kind is EventKind.RETRY]
    assert len(retry_evs) == 1
    assert retry_evs[0].data["backoff_s"] == \
        slo.backoff(1, seed=3, request_id=0)
    # conservation through the retry: monitor fully drained
    assert sim.monitor.live_instances() == 0


def test_retry_skipped_when_deadline_already_lost():
    # the deadline admits the request (service fits) but the huge
    # backoff would land the retry beyond release + deadline, so the
    # retry is not even scheduled
    slo = SLOClass("hopeless", deadline_s=0.6, timeout_s=0.1,
                   retry_budget=5, backoff_base_s=10.0)
    reqs = [SimRequest(rid=0, release=0.0, prompt=100, new=80, slo=slo)]
    sim = SimServing(_model(), reqs, policy="busy").run()
    assert sim.requests[0].outcome == "timed_out"
    assert sim.requests[0].tries == 1
    assert sim.report("t").serving["retries"] == 0


def test_hedge_wins_over_straggler_and_cancels_loser():
    # replica 0 (dispatch-preferred) straggles 20x; the hedge fires on
    # replica 1 and finishes long before the primary would have
    slo = SLOClass("hedgy", deadline_s=60.0, timeout_s=50.0,
                   hedge_after_s=0.2)
    reqs = [SimRequest(rid=0, release=0.0, prompt=160, new=80, slo=slo)]
    timeline = ConditionTimeline([straggler(0.0, core=0, slowdown=20.0)])
    model = ServingModel(machine=MachineModel(name="duo", n_cores=2),
                         slots_per_replica=1)
    sim = SimServing(model, reqs, policy="busy",
                     conditions=timeline).run()
    req = sim.requests[0]
    rep = sim.report("t")
    assert req.outcome == "completed"
    assert rep.serving["hedges"] == 1
    assert rep.serving["hedge_wins"] == 1
    # base service is 0.54 s; the straggling primary alone would need
    # 10.8 s — completion proves the hedge won and was not cancelled
    assert req.done_at < 2.0
    # first completion cancelled the loser: no live attempts or busy
    # slots remain, and the monitor accounts exactly one completion
    assert sim._active == 0
    assert sim._busy == [0, 0]
    assert sim.monitor.live_instances() == 0
    assert sim.monitor.completed_instances() == 1


def test_hedge_not_issued_without_protection():
    slo = SLOClass("hedgy", deadline_s=60.0, timeout_s=50.0,
                   hedge_after_s=0.2)
    reqs = [SimRequest(rid=0, release=0.0, prompt=160, new=80, slo=slo)]
    timeline = ConditionTimeline([straggler(0.0, core=0, slowdown=20.0)])
    model = ServingModel(machine=MachineModel(name="duo", n_cores=2),
                         slots_per_replica=1)
    sim = SimServing(model, reqs, policy="busy", protection=False,
                     conditions=timeline).run()
    assert sim.report("t").serving["hedges"] == 0
    assert sim.requests[0].outcome == "completed"   # slow, but done


def test_core_fail_requeues_uncharged_and_recovers():
    # the failing replica's attempt is torn off and requeued without a
    # retry-budget debit; the request completes elsewhere
    slo = SLOClass("std", deadline_s=60.0, timeout_s=50.0, retry_budget=0)
    reqs = [SimRequest(rid=0, release=0.0, prompt=160, new=160, slo=slo)]
    timeline = ConditionTimeline([core_fail(0.3, core=0),
                                  core_recover(5.0, core=0)])
    model = ServingModel(machine=MachineModel(name="duo", n_cores=2),
                         slots_per_replica=1)
    bus = EventBus()
    rec = TraceRecorder(bus)
    sim = SimServing(model, reqs, policy="busy", conditions=timeline,
                     bus=bus).run()
    req = sim.requests[0]
    rep = sim.report("t")
    assert req.outcome == "completed"
    assert req.tries == 1                      # uncharged
    assert rep.serving["requeues"] == 1
    assert rep.serving["retries"] == 0
    modes = [e.data["mode"] for e in rec.events
             if e.kind is EventKind.DEGRADE]
    assert "quarantine" in modes
    requeue_evs = [e for e in rec.events if e.kind is EventKind.RETRY]
    assert requeue_evs and requeue_evs[0].data.get("requeued") is True


def test_power_cap_protected_zero_violation_and_brownout():
    # tiny homogeneous machine: 4 replicas at (1.0 active, 0.1 idle);
    # a 2.5 W cap allows exactly 2 hot (budget 2.1, step 0.9)
    slo_mix = [BATCH if i % 2 else STANDARD for i in range(40)]
    reqs = [SimRequest(rid=i, release=0.05 * i, prompt=100, new=64,
                       slo=slo_mix[i]) for i in range(40)]
    timeline = ConditionTimeline([power_cap(0.5, 2.5)])
    bus = EventBus()
    rec = TraceRecorder(bus)
    sim = SimServing(_model(), reqs, policy="busy",
                     conditions=timeline, bus=bus).run()
    rep = sim.report("protected")
    assert rep.cap_violation_s == 0.0
    # best-effort requests admitted under the cap brown out to 16 tokens
    browned = [r for r in sim.requests
               if r.outcome == "completed" and r.slo is BATCH
               and r.tokens_out == 16]
    assert browned
    assert rep.serving["truncated_tokens"] >= 48 * len(browned)
    modes = [e.data["mode"] for e in rec.events
             if e.kind is EventKind.DEGRADE]
    assert "brownout" in modes
    allowance_ev = next(e for e in rec.events
                        if e.kind is EventKind.DEGRADE
                        and e.data["mode"] == "brownout")
    assert allowance_ev.data["allowance"] == 2


def test_power_cap_unprotected_violates():
    reqs = [SimRequest(rid=i, release=0.05 * i, prompt=100, new=64,
                       slo=STANDARD) for i in range(40)]
    timeline = ConditionTimeline([power_cap(0.5, 2.5)])
    sim = SimServing(_model(), reqs, policy="busy", protection=False,
                     conditions=timeline).run()
    # busy policy keeps all 4 replicas hot at >= 1.0 W past the cap
    assert sim.report("unprotected").cap_violation_s > 0.0


def test_queue_full_evicts_lowest_priority_victim():
    # one slot, an in-flight request, queue bound 2: two batch
    # requests fill the queue; an interactive arrival evicts the
    # youngest batch request instead of being shed itself
    model = ServingModel(machine=MachineModel(name="solo", n_cores=1),
                         slots_per_replica=1)
    long_slo = SLOClass("std", deadline_s=60.0, timeout_s=50.0)
    reqs = [
        SimRequest(rid=0, release=0.0, prompt=100, new=160, slo=long_slo),
        SimRequest(rid=1, release=0.01, prompt=100, new=32, slo=BATCH),
        SimRequest(rid=2, release=0.02, prompt=100, new=32, slo=BATCH),
        SimRequest(rid=3, release=0.03, prompt=100, new=32,
                   slo=SLOClass("vip", deadline_s=60.0, priority=9)),
    ]
    adm = AdmissionController(max_queue_depth=2)
    sim = SimServing(model, reqs, policy="busy", admission=adm).run()
    by_id = {r.rid: r for r in sim.requests}
    assert by_id[2].outcome == "shed"          # youngest lowest-pri
    assert by_id[3].outcome == "completed"     # admitted over it
    assert by_id[1].outcome == "completed"
    assert sim.report("t").serving["shed_by_reason"] == {"queue": 1}


def test_protection_off_no_slo_is_plain_fifo():
    # no SLOs, protection off, no perturbations: every request
    # completes, and none of the protection event kinds fire
    reqs = [SimRequest(rid=i, release=0.05 * i, prompt=100, new=32)
            for i in range(30)]
    bus = EventBus()
    rec = TraceRecorder(bus)
    sim = SimServing(_model(), reqs, protection=False, bus=bus).run()
    rep = sim.report("plain")
    assert rep.serving["completed"] == 30
    assert rep.serving["shed"] == 0
    assert rep.serving["retries"] == 0
    assert rep.serving["hedges"] == 0
    assert rep.serving["degrades"] == 0
    protection_kinds = {EventKind.SHED, EventKind.RETRY,
                        EventKind.HEDGE, EventKind.DEGRADE}
    assert not [e for e in rec.events if e.kind in protection_kinds]
    # the serving extras stay out of the report repr, so pre-serving
    # report printing (and tests asserting on it) is unchanged
    assert "serving" not in repr(rep)


# ---------------------------------------------------------------------------
# Conservation invariant
# ---------------------------------------------------------------------------

_TIMELINES = [
    ConditionTimeline(),
    ConditionTimeline([power_cap(1.0, 2.5), power_cap(3.0, None)]),
    ConditionTimeline([core_fail(0.5, core=0), core_recover(2.0, core=0),
                       thermal_throttle(1.0, core_type="", freq=None)]),
    ConditionTimeline([straggler(0.2, core=1, slowdown=8.0),
                       power_cap(1.5, 2.5), core_fail(2.0, core=3)]),
]


def _assert_conserved(sim: SimServing, n: int) -> None:
    reqs = sim.requests
    assert len(reqs) == n
    # every request ends in exactly one terminal outcome, stamped
    outcomes = {"completed": 0, "shed": 0, "timed_out": 0}
    for r in reqs:
        assert r.outcome in outcomes
        outcomes[r.outcome] += 1
        assert r.done_at is not None and r.done_at >= r.release
    rep = sim.report("conserve")
    s = rep.serving
    assert outcomes["completed"] == s["completed"]
    assert outcomes["shed"] == s["shed"]
    assert outcomes["timed_out"] == s["timed_out"]
    assert sum(outcomes.values()) == s["requests"] == n
    assert sum(s["shed_by_reason"].values()) == \
        outcomes["shed"] + outcomes["timed_out"]
    # the monitor drained: nothing ready or executing survives the run
    assert sim.monitor.live_instances() == 0
    assert sim.monitor.completed_instances() == s["completed"]
    assert sim.monitor.shed_instances() == \
        outcomes["shed"] + outcomes["timed_out"]
    # no attempt leaked a slot
    assert sim._active == 0
    assert all(b == 0 for b in sim._busy)


def _conservation_run(seed: int, timeline: ConditionTimeline,
                      protection: bool) -> None:
    n = 250
    # ~3x the tiny machine's capacity: admission, timeouts, retries and
    # hedges all fire
    reqs = build_requests(PoissonArrivals(rate=45.0, seed=seed), n,
                          seed=seed)
    sim = SimServing(_model(), reqs, policy="prediction", rate_s=0.25,
                     protection=protection, conditions=timeline,
                     seed=seed)
    sim.run()
    _assert_conserved(sim, n)


@pytest.mark.parametrize("timeline", _TIMELINES)
@pytest.mark.parametrize("protection", [True, False])
def test_conservation_fixed_seeds(timeline, protection):
    _conservation_run(11, timeline, protection)


@given(st.integers(0, 2 ** 16), st.integers(0, len(_TIMELINES) - 1),
       st.booleans())
@settings(max_examples=15, deadline=None)
def test_conservation_property(seed, tidx, protection):
    _conservation_run(seed, _TIMELINES[tidx], protection)


# ---------------------------------------------------------------------------
# Trace round trip: sim -> trace -> sim, byte-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("machine", [TINY, HYBRID_PE])
def test_replay_byte_exact(tmp_path, machine):
    from repro.serving import replay_serving
    n = 300
    reqs = build_requests(PoissonArrivals(rate=60.0, seed=5), n, seed=5)
    timeline = ConditionTimeline([
        straggler(0.3, core=0, slowdown=5.0),
        power_cap(1.0, 0.25 * machine.n_cores),
        core_fail(1.5, core=1), core_recover(3.0, core=1),
        power_cap(4.0, None),
    ])
    kwargs = dict(policy="prediction", rate_s=0.25, seed=5)
    model = ServingModel(machine=machine, slots_per_replica=2)

    bus1 = EventBus()
    rec1 = TraceRecorder(bus1)
    SimServing(model, reqs, conditions=timeline, bus=bus1, **kwargs).run()
    p1 = rec1.to_jsonl(tmp_path / "orig.jsonl")

    loaded = TraceRecorder.from_jsonl(p1)
    bus2 = EventBus()
    rec2 = TraceRecorder(bus2)
    replay_serving(loaded.merged_events(), model, bus=bus2,
                   **kwargs).run()
    p2 = rec2.to_jsonl(tmp_path / "replay.jsonl")

    assert p1.read_bytes() == p2.read_bytes()
    # sanity: the trace is substantial and carries the SLO contracts
    lines = p1.read_text().splitlines()
    assert len(lines) > n
    subs = [json.loads(ln) for ln in lines
            if json.loads(ln)["kind"] == "task_submitted"]
    assert len(subs) == n
    assert any("slo" in d["data"] for d in subs)


# ---------------------------------------------------------------------------
# ServingEngine satellites: injected clock, per-engine ids, diagnostics
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_setup():
    import jax
    from repro.configs import get_smoke_config
    from repro.models import init_params
    cfg = get_smoke_config("llama3.2-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class _VirtualClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        self.now += 0.125
        return self.now


def test_engine_injected_clock(engine_setup):
    from repro.serving import ServingEngine, Request
    cfg, params = engine_setup
    clock = _VirtualClock()
    bus = EventBus()
    rec = TraceRecorder(bus)
    engine = ServingEngine(cfg, params, max_batch=2, bus=bus, clock=clock)
    req = engine.submit(Request(prompt=[1, 2, 3]))
    # every timestamp is a tick of the injected clock — no wall time
    assert req.submitted_at == 0.125   # the injected clock's first tick
    assert all(ev.time % 0.125 == 0.0 for ev in rec.events)


def test_engine_ids_are_per_engine(engine_setup):
    from repro.serving import ServingEngine, Request
    cfg, params = engine_setup
    e1 = ServingEngine(cfg, params, max_batch=2)
    e2 = ServingEngine(cfg, params, max_batch=2)
    r1 = e1.submit(Request(prompt=[1, 2]))
    r2 = e2.submit(Request(prompt=[3, 4]))
    r3 = e1.submit(Request(prompt=[5, 6]))
    # two engines no longer interleave a module-global counter
    assert (r1.request_id, r3.request_id) == (0, 1)
    assert r2.request_id == 0


def test_engine_drain_diagnostics(engine_setup):
    from repro.serving import ServingEngine, Request
    cfg, params = engine_setup
    engine = ServingEngine(cfg, params, max_batch=2)
    engine.submit(Request(prompt=[1, 2, 3]))
    with pytest.raises(RuntimeError, match=r"1 queued, 0 active slots"):
        engine.run_until_drained(max_ticks=0)


def test_engine_admission_shed(engine_setup):
    from repro.serving import ServingEngine, Request
    cfg, params = engine_setup
    bus = EventBus()
    rec = TraceRecorder(bus)
    engine = ServingEngine(
        cfg, params, max_batch=2, bus=bus,
        admission=AdmissionController(max_queue_depth=1))
    kept = engine.submit(Request(prompt=[1, 2]))
    shed = engine.submit(Request(prompt=[3, 4]))
    assert kept in engine.queue
    assert shed in engine.shed and shed.done
    assert engine.monitor.shed_instances() == 1
    shed_evs = [e for e in rec.events if e.kind is EventKind.SHED]
    assert len(shed_evs) == 1
    assert shed_evs[0].data["reason"] == "queue"


def test_engine_brownout_truncates_best_effort(engine_setup):
    from repro.serving import ServingEngine, Request
    cfg, params = engine_setup
    bus = EventBus()
    rec = TraceRecorder(bus)
    engine = ServingEngine(cfg, params, max_batch=2, bus=bus,
                           brownout_tokens=4)
    req = engine.submit(Request(prompt=[1, 2], max_new_tokens=32,
                                slo=BATCH))
    assert req.max_new_tokens == 4
    kinds = [e.kind for e in rec.events]
    # DEGRADE lands between SUBMITTED and READY, after the truncation,
    # so the monitor only ever sees the browned-out cost
    assert kinds == [EventKind.TASK_SUBMITTED, EventKind.DEGRADE,
                     EventKind.TASK_READY]
    # non-best-effort traffic is untouched
    std = engine.submit(Request(prompt=[1, 2], max_new_tokens=32,
                                slo=STANDARD))
    assert std.max_new_tokens == 32

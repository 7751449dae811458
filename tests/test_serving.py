"""Serving engine: greedy correctness, continuous batching, autoscaler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import forward, init_params
from repro.serving import AutoScaler, Request, ServingEngine

CFG = get_smoke_config("llama3.2-1b")
KEY = jax.random.PRNGKey(0)
PARAMS = init_params(KEY, CFG)


def _greedy_reference(prompt, n_new):
    toks = list(prompt)
    for _ in range(n_new):
        logits, _ = forward(PARAMS, jnp.asarray([toks], jnp.int32), CFG)
        toks.append(int(jnp.argmax(logits[0, -1, :CFG.vocab])))
    return toks[len(prompt):]


@pytest.mark.slow
def test_single_request_matches_reference():
    engine = ServingEngine(CFG, PARAMS, max_batch=2, max_len=64)
    req = engine.submit(Request(prompt=[5, 9, 2, 7], max_new_tokens=6))
    engine.run_until_drained()
    assert req.done
    assert req.output == _greedy_reference([5, 9, 2, 7], 6)


@pytest.mark.slow
def test_continuous_batching_mixed_lengths():
    engine = ServingEngine(CFG, PARAMS, max_batch=2, max_len=64)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]
    reqs = [engine.submit(Request(prompt=p, max_new_tokens=4))
            for p in prompts]
    engine.run_until_drained()
    for p, r in zip(prompts, reqs):
        assert r.done
        assert r.output == _greedy_reference(p, 4), p


def test_slots_freed_and_reused():
    engine = ServingEngine(CFG, PARAMS, max_batch=1, max_len=64)
    reqs = [engine.submit(Request(prompt=[i + 1], max_new_tokens=3))
            for i in range(3)]
    engine.run_until_drained()
    assert all(r.done for r in reqs)
    # serialized through one slot: completion order == arrival order
    times = [r.done_at for r in reqs]
    assert times == sorted(times)


@pytest.mark.parametrize("n,fits", [(20, True), (32, False), (40, False)])
def test_submit_rejects_prompt_that_does_not_fit(n, fits):
    """A prompt fits when its prefill bucket is within max_len and its
    first decode position is inside the cache; one that does not is
    refused at submit instead of being truncated by the ring branch of
    prefill (40 tokens bucket to 64, which is 2 × max_len)."""
    engine = ServingEngine(CFG, PARAMS, max_batch=1, max_len=32)
    req = Request(prompt=list(range(1, n + 1)), max_new_tokens=2)
    if fits:
        engine.submit(req)
        engine.run_until_drained()
        assert req.done and len(req.output) == 2
        return
    with pytest.raises(ValueError, match="max_len=32"):
        engine.submit(req)
    assert not engine.queue and req.request_id is None


def test_autoscaler_tracks_load():
    monitor_engine = ServingEngine(CFG, PARAMS, max_batch=4, max_len=64)
    scaler = AutoScaler(monitor_engine.monitor, max_replicas=4,
                        policy="prediction")
    # no load ⇒ scale to zero
    assert scaler.target(0, 0) == 0
    # queue load ⇒ scale out (count-based until α is learned)
    for i in range(8):
        monitor_engine.submit(Request(prompt=[1, 2], max_new_tokens=2))
    assert scaler.target(8, 0) >= 1
    monitor_engine.run_until_drained()
    assert scaler.target(0, 0) == 0


def test_autoscaler_policies_differ():
    engine = ServingEngine(CFG, PARAMS, max_batch=4, max_len=64)
    busy = AutoScaler(engine.monitor, 4, policy="busy")
    idle = AutoScaler(engine.monitor, 4, policy="idle")
    assert busy.target(0, 0) == 4
    assert idle.target(0, 0) == 0
    assert idle.target(2, 1) == 3


def test_autoscaler_never_exceeds_max_replicas_when_oversubscribed():
    """Regression: a prediction stack configured with the DLB-style
    oversubscribing Alg. 1 must still cap the serving target at the
    replicas the deployment owns."""
    from repro.core.governor import GovernorSpec
    from repro.core.monitoring import TaskMonitor
    from repro.core.prediction import PredictionConfig

    monitor = TaskMonitor(min_samples=1)
    scaler = AutoScaler(monitor, max_replicas=4, spec=GovernorSpec(
        resources=4, policy="prediction", monitoring=True,
        prediction=PredictionConfig(min_samples=1, rate_s=50e-6,
                                    allow_oversubscription=True,
                                    oversubscription_cap=4.0)))
    for i in range(3):
        monitor.on_task_ready(i, "req", 1.0)
        monitor.on_task_execute(i, "req", 1.0)
        monitor.on_task_completed(i, "req", 1.0, 50e-6)
    for i in range(12):                    # far more work than replicas
        monitor.on_task_ready(100 + i, "req", 1.0)
    assert scaler.predictor.compute_delta() > 4   # Δ oversubscribes...
    assert scaler.target(12, 0) == 4              # ...the target cannot


def _served(bus=None, n=2):
    engine = ServingEngine(CFG, PARAMS, max_batch=1, max_len=64, bus=bus)
    reqs = [engine.submit(Request(prompt=[i + 1, 2, 3], max_new_tokens=3))
            for i in range(n)]
    engine.run_until_drained()
    return engine, reqs


def test_engine_spans_reach_a_span_subscriber():
    from repro.core import EventBus, EventKind

    bus = EventBus()
    got = []
    bus.subscribe(got.append, kinds=[EventKind.SPAN])
    engine, reqs = _served(bus)
    by = {}
    for ev in got:
        by.setdefault(ev.type_name, []).append(ev)
    assert set(by) == {"engine.admit", "engine.prefill", "engine.first_token",
                       "engine.scatter", "engine.decode",
                       "engine.decode.dispatch", "engine.decode.readback",
                       "engine.decode.finish", "engine.counts"}
    assert [ev.task_id for ev in by["engine.admit"]] == \
        [r.request_id for r in reqs]
    for ev, r in zip(by["engine.admit"], reqs):
        assert ev.data["queue_ms"] == (r.admitted_at - r.submitted_at) * 1e3
        assert ev.data["parent"] is None and ev.elapsed >= 0
    assert {ev.data["parent"] for ev in by["engine.prefill"]} == \
        {"engine.admit"}
    assert {ev.data["parent"] for ev in by["engine.decode.readback"]} == \
        {"engine.decode"}
    assert all(ev.task_id is None for ev in by["engine.decode"])
    # a span ends before its parent does, and counts close each parent;
    # an admission reads its first token, a decode step its tokens
    assert [ev.data["parent"] for ev in by["engine.counts"]] == \
        ["engine.admit", "engine.decode", "engine.decode"] * 2
    assert sum(ev.data["syncs"] for ev in by["engine.counts"]) == \
        engine.host_syncs == 2 * (1 + 1 + 1)


def test_engine_builds_no_span_event_without_a_span_subscriber(monkeypatch):
    import repro.serving.engine as engine_mod
    from repro.core import EventBus, EventKind

    built, event = [], engine_mod.RuntimeEvent

    def counting(*args, **kw):
        built.append(kw["kind"])
        return event(*args, **kw)

    monkeypatch.setattr(engine_mod, "RuntimeEvent", counting)
    bus = EventBus()
    done = []
    bus.subscribe(done.append, kinds=[EventKind.TASK_COMPLETED])
    engine, reqs = _served(bus)
    assert all(r.done for r in reqs) and done
    assert EventKind.SPAN not in built and built


def test_monitor_sees_requests_and_prefills_only():
    """Ticks publish no task events: the monitor holds no decode-tick
    type, and request ids run on without gaps between ticks."""
    engine, reqs = _served()
    assert sorted(engine.monitor.type_names()) == ["prefill", "request"]
    assert [r.request_id for r in reqs] == [0, 1]
    late = engine.submit(Request(prompt=[4, 5], max_new_tokens=2))
    assert late.request_id == 2 and engine.ticks > 0
    assert all(r.admitted_at >= r.submitted_at for r in reqs)


@pytest.mark.parametrize("live", [1, 2, 3, 4])
def test_a_decode_tick_reads_the_device_once(live):
    """However many slots are live, a decode step makes one device→host
    read, its tokens: ``syncs`` of every ``engine.decode`` is 1, and
    ``host_syncs`` grows by one a tick past the admissions' first
    tokens."""
    from repro.core import EventBus, EventKind

    bus = EventBus()
    got = []
    bus.subscribe(got.append, kinds=[EventKind.SPAN])
    engine = ServingEngine(CFG, PARAMS, max_batch=4, max_len=64, bus=bus)
    for i in range(live):
        engine.submit(Request(prompt=[i + 1, 2, 3], max_new_tokens=5))
    engine.tick()                          # admits every request
    assert engine.host_syncs == live + 1
    for _ in range(3):
        before = engine.host_syncs
        assert engine.tick() == live
        assert engine.host_syncs == before + 1
    decode = [ev for ev in got if ev.type_name == "engine.counts"
              and ev.data["parent"] == "engine.decode"]
    assert [ev.data["syncs"] for ev in decode] == [1] * 4


def test_a_request_past_the_cache_end_stops_at_the_last_position():
    """A budget longer than the cache allows ends where the positions
    do, on the host's count, with the greedy reference's tokens."""
    prompt = list(range(3, 23))
    engine = ServingEngine(CFG, PARAMS, max_batch=2, max_len=32)
    req = engine.submit(Request(prompt=prompt, max_new_tokens=100))
    engine.run_until_drained()
    assert req.done and len(req.output) == 32 - len(prompt)
    assert engine.ticks == len(req.output) - 1
    assert req.output == _greedy_reference(prompt, len(req.output))


def test_host_positions_follow_prompt_and_ticks():
    """Each live slot's host position is its prompt length plus the
    ticks since its admission (the admitting tick included), and the
    decode program sees one shape throughout."""
    engine = ServingEngine(CFG, PARAMS, max_batch=3, max_len=64)
    admitted = {}                          # request -> tick it entered

    def submit(n):
        req = engine.submit(Request(prompt=list(range(1, n + 1)),
                                    max_new_tokens=50))
        admitted[id(req)] = (req, engine.ticks + 1)

    submit(3)
    engine.tick()
    engine.tick()
    submit(7)
    submit(18)
    for _ in range(3):
        engine.tick()
        for slot, req in enumerate(engine.active):
            r, t = admitted[id(req)]
            assert engine.pos[slot] == len(r.prompt) + engine.ticks - t + 1
    assert sum(r is not None for r in engine.active) == 3
    assert engine.pos.dtype == np.int32 and engine.pos.shape == (3,)
    assert engine._decode._cache_size() == 1

"""Trace record/replay: the round-trip property and the exports.

The acceptance property: a closed graph executed on ``SimExecutor`` with
a ``TraceRecorder`` attached, replayed via ``TraceReplayer`` under the
same ``GovernorSpec``, reproduces the same per-policy decision sequence
and report.
"""

import json
import random

import pytest

from repro.core import EventBus, EventKind, GovernorSpec
from repro.runtime import MN4, SimExecutor, Task, TaskGraph, ThreadExecutor
from repro.trace import (TraceRecorder, TraceReplayer, decision_sequence,
                         prediction_sequence)
from repro.workloads import BurstArrivals


def mixed_graph(seed=0, n_waves=6, width=8):
    """Waves of parallel tasks separated by barriers — enough phase
    change to make every policy take real decisions."""
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


@pytest.mark.parametrize("policy", ["busy", "idle", "hybrid", "prediction"])
def test_sim_round_trip_reproduces_run(policy):
    spec = GovernorSpec(resources=8, policy=policy, monitoring=True)
    ex = SimExecutor(MN4, spec=spec)
    rec = TraceRecorder(bus=ex.bus)
    r1 = ex.run(mixed_graph())

    replayer = TraceReplayer(rec)
    bus2 = EventBus()
    rec2 = TraceRecorder(bus=bus2)
    r2 = replayer.replay(spec, machine=TraceReplayer.replay_machine(MN4),
                         bus=bus2)

    assert r2.tasks_completed == r1.tasks_completed
    assert r2.makespan == pytest.approx(r1.makespan, rel=1e-12)
    assert r2.energy == pytest.approx(r1.energy, rel=1e-12)
    assert r2.resumes == r1.resumes
    assert r2.idles == r1.idles
    assert decision_sequence(rec2.events) == decision_sequence(rec.events)


def test_prediction_events_published():
    spec = GovernorSpec(resources=8, policy="prediction", monitoring=True)
    ex = SimExecutor(MN4, spec=spec)
    rec = TraceRecorder(bus=ex.bus)
    r = ex.run(mixed_graph())
    deltas = prediction_sequence(rec.events)
    assert len(deltas) == r.predictions
    assert all(isinstance(d, int) for d in deltas)


def test_open_trace_preserves_arrival_timeline():
    g = TaskGraph()
    for _ in range(20):
        g.add(Task("w", cost=1.0, service_time=1e-4))
    ex = SimExecutor(MN4, policy="idle")
    rec = TraceRecorder(bus=ex.bus)
    ex.run(g, arrivals=BurstArrivals(burst_size=5, gap=0.01))
    g2, arrivals = TraceReplayer(rec).build()
    assert arrivals is not None
    assert len(g2) == 20
    # bursts of 5 separated by 10 ms, recorded faithfully
    ts = arrivals.times(20)
    assert ts[0] == pytest.approx(0.0, abs=1e-9)
    assert ts[5] == pytest.approx(0.01, rel=1e-6)


def test_closed_trace_builds_closed_graph():
    ex = SimExecutor(MN4, policy="busy")
    rec = TraceRecorder(bus=ex.bus)
    ex.run(mixed_graph())
    g2, arrivals = TraceReplayer(rec).build()
    assert arrivals is None
    assert all(t.release_time is None for t in g2.tasks)
    # dependency structure survives: per-wave barriers exist
    barriers = [t for t in g2.tasks if t.type_name == "barrier"]
    assert len(barriers) == 6
    assert all(len(b.deps) == 8 for b in barriers)


def test_thread_trace_replays_in_sim():
    ex = ThreadExecutor(3, policy="idle")
    rec = TraceRecorder(bus=ex.bus)
    g = TaskGraph()
    for i in range(12):
        g.add(Task("w", cost=1.0, fn=lambda: None))
    r_live = ex.run(g)
    assert r_live.tasks_completed == 12 or r_live.accuracy is None
    spec = GovernorSpec(resources=3, policy="prediction", monitoring=True)
    r_sim = TraceReplayer(rec).replay(spec)
    assert r_sim.tasks_completed == 12
    assert r_sim.makespan > 0


def test_jsonl_round_trip(tmp_path):
    ex = SimExecutor(MN4, policy="hybrid", monitoring=True)
    rec = TraceRecorder(bus=ex.bus)
    r1 = ex.run(mixed_graph())
    path = rec.to_jsonl(tmp_path / "trace.jsonl")
    rec2 = TraceRecorder.from_jsonl(path)
    assert len(rec2.events) == len(rec.events)
    assert rec2.events[0] == rec.events[0]
    spec = GovernorSpec(resources=8, policy="hybrid", monitoring=True)
    r2 = TraceReplayer(path).replay(
        spec, machine=TraceReplayer.replay_machine(MN4))
    assert r2.makespan == pytest.approx(r1.makespan, rel=1e-12)


def test_chrome_export(tmp_path):
    ex = SimExecutor(MN4, policy="prediction", monitoring=True)
    rec = TraceRecorder(bus=ex.bus)
    r = ex.run(mixed_graph())
    path = rec.to_chrome(tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(slices) == r.tasks_completed
    assert len(counters) == r.predictions
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)


def test_out_of_order_submission_keeps_dependencies():
    """Open-mode submission order need not be topological: a dependent
    submitted before its dependency must keep the edge on replay."""
    ex = ThreadExecutor(2, policy="busy").start()
    rec = TraceRecorder(bus=ex.bus)
    a = Task("a", cost=1.0, fn=lambda: None)
    b = Task("b", cost=1.0, fn=lambda: None).depends_on(a)
    ex.submit(b)          # b first — blocked until a completes
    ex.submit(a)
    ex.close()
    g2, _ = TraceReplayer(rec).build()
    rebuilt_b = next(t for t in g2.tasks if t.type_name == "b")
    rebuilt_a = next(t for t in g2.tasks if t.type_name == "a")
    assert rebuilt_b.deps == [rebuilt_a]


def test_no_prediction_events_for_non_predictive_policies():
    """Thread-recorded busy/idle traces must match the simulator: no
    predictor ⇒ no PREDICTION events (the ticker still runs)."""
    ex = ThreadExecutor(2, policy="busy")
    rec = TraceRecorder(bus=ex.bus)
    g = TaskGraph()
    for _ in range(4):
        g.add(Task("w", cost=1.0, service_time=2e-3))
    rep = ex.run(g)
    assert rep.predictions == 0
    assert prediction_sequence(rec.events) == []


def test_pull_governor_publishes_prediction_on_target():
    """Pull-style frontends (autoscaler) have no tick loop: target()
    decisions are their prediction samples on the bus."""
    from repro.core import EventBus, ResourceGovernor, TaskMonitor

    bus = EventBus()
    rec = TraceRecorder(bus=bus)
    mon = TaskMonitor()
    gov = ResourceGovernor(
        GovernorSpec(resources=4, policy="prediction", monitoring=True),
        monitor=mon, bus=bus)
    gov.target(queued=3, active=1)
    gov.target(queued=0, active=0)
    assert len(prediction_sequence(rec.events)) == 2
    # ...but non-predictive policies stay silent, matching the sim
    rec.clear()
    gov2 = ResourceGovernor(GovernorSpec(resources=4, policy="busy"),
                            bus=bus)
    gov2.target(queued=3, active=1)
    assert prediction_sequence(rec.events) == []


def test_thread_executor_honors_prestamped_release_times():
    """Frontend parity: a graph carrying release_times (e.g. from a
    replayed trace) runs open on threads, like in the simulator."""
    g = TaskGraph()
    out = []
    for i in range(4):
        g.add(Task("w", cost=1.0, fn=lambda i=i: out.append(i)))
    for t, rt in zip(g.tasks, (0.0, 0.0, 0.03, 0.06)):
        t.release_time = rt
    rep = ThreadExecutor(2, policy="busy").run(g)
    assert sorted(out) == list(range(4))
    assert rep.makespan >= 0.06


def test_serving_sojourn_not_replayed_as_service_time():
    """A serving request's COMPLETED elapsed is its sojourn (queueing
    included); replay must use the EXECUTE→COMPLETED holding time."""
    from repro.core import RuntimeEvent

    events = [
        RuntimeEvent(kind=EventKind.TASK_SUBMITTED, time=0.0, task_id=1,
                     type_name="request", cost=4.0, data={"deps": []}),
        # admitted 2 s after submission, finished 1 s later: elapsed
        # publishes the 3 s sojourn, but the slot was held for 1 s
        RuntimeEvent(kind=EventKind.TASK_EXECUTE, time=2.0, task_id=1,
                     type_name="request", cost=4.0),
        RuntimeEvent(kind=EventKind.TASK_COMPLETED, time=3.0, task_id=1,
                     type_name="request", cost=4.0, elapsed=3.0),
    ]
    g, _ = TraceReplayer(events).build()
    assert g.tasks[0].service_time == pytest.approx(1.0)


def test_reused_executor_does_not_accumulate_subscribers():
    ex = SimExecutor(MN4, policy="prediction", monitoring=True)
    rec = TraceRecorder(bus=ex.bus)
    for _ in range(3):
        ex.run(mixed_graph(n_waves=2, width=2))
    # only the recorder remains subscribed; per-run monitors detached
    assert ex.bus.n_subscribers == 1
    assert len(rec.events) > 0


def test_recorder_attach_idempotent():
    ex = SimExecutor(MN4, policy="busy")
    rec = TraceRecorder(bus=ex.bus)
    rec.attach(ex.bus)                     # second attach is a no-op
    g = TaskGraph()
    g.add(Task("w", cost=1.0, service_time=1e-5))
    ex.run(g)
    g2, _ = TraceReplayer(rec).build()
    assert len(g2) == 1                    # not double-recorded


def test_unreplayable_trace_rejected():
    bus = EventBus()
    rec = TraceRecorder(bus=bus)
    from repro.core import RuntimeEvent
    bus.publish(RuntimeEvent(kind=EventKind.TASK_SUBMITTED, time=0.0,
                             task_id=1, type_name="t", cost=1.0,
                             data={"deps": []}))
    with pytest.raises(ValueError, match="never completed"):
        TraceReplayer(rec).build()


def test_chrome_export_renders_spans(tmp_path):
    from repro.core import RuntimeEvent

    bus = EventBus()
    rec = TraceRecorder(bus=bus)
    bus.publish(RuntimeEvent(kind=EventKind.SPAN, time=2.0, task_id=3,
                             type_name="engine.admit", elapsed=1.5,
                             data={"queue_ms": 4.0, "parent": None}))
    bus.publish(RuntimeEvent(kind=EventKind.SPAN, time=1.0, task_id=3,
                             type_name="engine.prefill", elapsed=0.25,
                             data={"parent": "engine.admit"}))
    doc = json.loads(rec.to_chrome(tmp_path / "t.json").read_text())
    admit, prefill = doc["traceEvents"]
    # the trace opens where the first span began
    assert admit == {"name": "engine.admit", "ph": "X", "ts": 0.0,
                     "dur": 1.5e6, "pid": 0, "tid": 0,
                     "args": {"task_id": 3, "queue_ms": 4.0,
                              "parent": None}}
    assert prefill["name"] == "engine.prefill" and prefill["ph"] == "X"
    assert prefill["ts"] == pytest.approx(0.25e6)
    assert prefill["dur"] == pytest.approx(0.25e6)
    assert prefill["args"]["parent"] == "engine.admit"

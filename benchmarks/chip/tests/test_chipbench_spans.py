"""The engine's own spans read from a profiler trace (``spans``): a tiny
``ServingEngine`` traced on the CPU, idle time given to the innermost
span on synthetic intervals, the recorded chip trace, and the readers of
queue wait, host syncs and the decode step's roofline share."""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

from benchmarks.chip import flops, peaks, spans, spec, trace  # noqa: E402
from benchmarks.chip.arrivals import Planned  # noqa: E402
from benchmarks.chip.driver import Run, Served  # noqa: E402
from benchmarks.chip.record import Record  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "dsc33b-8l.xplane.pb.gz"
CHILDREN = {
    "engine.admit": ("engine.prefill", "engine.first_token",
                     "engine.scatter"),
    "engine.decode": ("engine.decode.dispatch", "engine.decode.readback",
                      "engine.decode.finish"),
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three requests through two slots, each tick inside the driver's
    span, traced by ``jax.profiler`` after a warm-up."""
    cfg = get_smoke_config("llama3.2-1b")
    eng = ServingEngine(cfg, init_params(jax.random.PRNGKey(0), cfg),
                        max_batch=2, max_len=64)
    for n in (5, 20):                      # both prefill buckets
        eng.submit(Request(prompt=[1] * n, max_new_tokens=2))
    eng.run_until_drained()
    before = eng.host_syncs
    log_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        reqs = [eng.submit(Request(prompt=list(range(1, n + 1)),
                                   max_new_tokens=m))
                for n, m in ((5, 3), (20, 4), (3, 2))]
        while eng.load:
            admit = bool(eng.queue) and any(r is None for r in eng.active)
            with jax.profiler.TraceAnnotation(
                    "tick.admit" if admit else "tick.decode"):
                eng.tick()
    finally:
        jax.profiler.stop_trace()
    path = trace.newest_xplane(log_dir)
    return eng, reqs, eng.host_syncs - before, path, spans.read(path)


def inside(child, parents):
    return [p for p in parents if p.start <= child.start
            and child.end <= p.end]


def test_every_engine_span_is_there_and_nested_as_documented(traced):
    eng, reqs, _, path, et = traced
    names = {s.name for s in et.spans}
    assert names == set(CHILDREN) | {c for cs in CHILDREN.values()
                                     for c in cs}
    for parent, children in CHILDREN.items():
        outer = et.named(parent)
        for name in children:
            got = et.named(name)
            assert len(got) == len(outer)
            assert all(len(inside(c, outer)) == 1 for c in got), name
    # admissions sit in the driver's admitting ticks, decode steps in
    # every tick
    _, host = trace.read_xplane(path)
    lo = min(s for _, s, _ in host)
    ticks = {n: [spans.EngineSpan(n, (s - lo) / 1e9, (e - lo) / 1e9)
                 for m, s, e in host if m == n]
             for n in ("tick.admit", "tick.decode")}
    assert all(inside(a, ticks["tick.admit"])
               for a in et.named("engine.admit"))
    assert len(et.named("engine.decode")) == sum(map(len, ticks.values()))
    # the CPU trace has no device plane
    assert et.programs == {} and et.idle_self == {}
    assert all(s.idle is None for s in et.spans)


def test_admission_arguments_and_queue_wait(traced):
    eng, reqs, _, _, et = traced
    admits = {s.args["req"]: s for s in et.named("engine.admit")}
    assert sorted(admits) == [r.request_id for r in reqs]
    for r in reqs:
        a = admits[r.request_id].args
        assert a["queue_ms"] == pytest.approx(
            (r.admitted_at - r.submitted_at) * 1e3, rel=1e-12)
        assert a["prompt"] == len(r.prompt)
        assert a["bucket"] == eng._prefill_len(len(r.prompt))
    # the third request waited for a slot
    assert admits[reqs[2].request_id].args["queue_ms"] > \
        admits[reqs[0].request_id].args["queue_ms"]


def test_syncs_count_the_read_sites_passed(traced):
    eng, _, total, _, et = traced
    # every admission reads its first token once
    assert [s.args["syncs"] for s in et.named("engine.admit")] == [1, 1, 1]
    # a tick reads its tokens and nothing more: the slot positions live
    # on the host, so no live slot adds a read
    decode = et.named("engine.decode")
    assert [s.args["live"] for s in decode] == [2, 2, 2]
    assert [s.args["syncs"] for s in decode] == [1, 1, 1]
    assert sum(s.args["syncs"] for s in et.spans if "syncs" in s.args) \
        == total == 6


def test_programs_are_named_for_their_functions(traced):
    eng, _, _, path, _ = traced
    host = {e.name for p in ProfileData.from_file(str(path)).planes
            for line in p.lines for e in line.events}
    assert {"PjitFunction(decode_step)", "PjitFunction(prefill)"} <= host
    # the module name is what the device trace's "XLA Modules" line shows
    decode = eng._decode.lower(eng.params, eng.tokens, eng.pos, eng.cache)
    prefill = eng._prefill.lower(eng.params, jnp.zeros((1, 16), jnp.int32))
    assert decode.as_text().startswith("module @jit_decode_step ")
    assert prefill.as_text().startswith("module @jit_prefill ")


def test_idle_time_goes_to_the_innermost_span():
    devices = {"/device:TPU:0": [(100, 300), (200, 400), (600, 700),
                                 (950, 1200)]}
    # busy 100-400, 600-700 and 950 to the stretch's end, 1000: 450 ns
    host = [("governor", 0, 40, {}),             # 40-50 in no span
            ("tick.decode", 50, 500, {}),
            ("engine.decode", 60, 480, {"live": 2}),
            ("engine.decode.dispatch", 60, 120, {}),
            ("engine.decode.readback", 120, 420, {}),
            ("engine.decode.finish", 420, 470, {}),
            ("engine.counts", 475, 475, {"syncs": 3}),
            ("driver", 500, 550, {}),
            ("tick.admit", 550, 1000, {}),
            ("engine.admit", 560, 990, {"req": 7, "queue_ms": 1.5}),
            ("engine.prefill", 560, 580, {}),
            ("engine.first_token", 580, 900, {}),
            ("engine.scatter", 900, 980, {}),
            ("engine.counts", 980, 980, {"syncs": 1})]
    et = spans.reduce(devices, host)
    ns = 1e-9
    want = {"governor": 40, trace.OUTSIDE: 10, "tick.decode": 10 + 20,
            "engine.decode": 10, "engine.decode.dispatch": 40,
            "engine.decode.readback": 20, "engine.decode.finish": 50,
            "driver": 50, "tick.admit": 10, "engine.admit": 0,
            "engine.prefill": 20, "engine.first_token": 220,
            "engine.scatter": 50}
    assert et.idle_self == {k: pytest.approx(v * ns) for k, v in want.items()}
    # all the stretch's idle time, once
    assert sum(et.idle_self.values()) == pytest.approx((1000 - 450) * ns)
    assert et.top_idle()[0] == ["engine.first_token", pytest.approx(220 * ns)]
    assert len(et.top_idle()) == trace.TOP
    # counts fold into the span they close; idle time inside each span
    (dec,) = et.named("engine.decode")
    assert dec.args == {"live": 2, "syncs": 3}
    assert dec.idle == pytest.approx((10 + 40 + 20 + 50) * ns)
    (adm,) = et.named("engine.admit")
    assert adm.args == {"req": 7, "queue_ms": 1.5, "syncs": 1}
    assert adm.start == pytest.approx(560 * ns)
    assert "engine.counts" not in {s.name for s in et.spans}
    # the stretch: driver spans that begin within 520 ns of the first
    part = spans.reduce(devices, host, seconds=520 * ns)
    assert {s.name for s in part.spans} == set(CHILDREN["engine.decode"]) \
        | {"engine.decode"}
    assert sum(part.idle_self.values()) == pytest.approx((550 - 300) * ns)


def test_recorded_chip_trace_idle_matches_the_driver_spans():
    """The recorded trace predates the engine's spans: every idle second
    falls to the driver's spans as ``trace.reduce`` gives them, and the
    programs are read with their names, not their fingerprints."""
    for seconds in (None, 0.1):
        et = spans.read(RECORDED, seconds=seconds)
        r = trace.reduce(*trace.read_xplane(RECORDED), seconds=seconds)
        assert et.spans == []
        assert et.idle_self == {k: pytest.approx(v, abs=1e-12)
                                for k, v in r["idle_gaps"]}
        assert sum(et.idle_self.values()) == pytest.approx(
            r["window_s"] - r["busy_s"])
    # five decode steps and one prefill, then named by fingerprint alone
    et = spans.read(RECORDED)
    r = trace.reduce(*trace.read_xplane(RECORDED))
    assert len(et.programs["jit__lambda"]) == 5 + 1
    assert all("(" not in name for name in et.programs)
    assert 0 < np.sum(et.programs["jit__lambda"]) <= r["busy_s"]


def _run(waits):
    """A window of 1 s opened at clock 100; ``waits`` are (admitted at,
    queue wait) in seconds after the opening, None for never admitted."""
    served = []
    for i, w in enumerate(waits):
        req = Request(prompt=[1, 2], max_new_tokens=2)
        if w is not None:
            at, q = w
            req.admitted_at = 100.0 + at
            req.submitted_at = req.admitted_at - q
        served.append(Served(Planned(0.1 * i, req.prompt, 2), 0.1 * i, req))
    return Run(1.0, served, served, [], 2.0, 100.0)


def test_queue_wait_reader():
    read = spec.metric_reader("queue_wait_ms.p90")
    rec = Record(run=_run([(0.1, 0.05), (0.2, 0.1), (0.5, 0.3),
                           (1.5, 2.0), None]),
                 model={}, setup_s=0.0, device_kind="x", seconds=1.0)
    # the three admitted in the window: p90 of 50, 100 and 300 ms
    assert read(rec) == pytest.approx(100 + 0.8 * 200)
    # an engine that stamps no admission gives nothing to read
    rec = Record(run=_run([None, None]), model={}, setup_s=0.0,
                 device_kind="x", seconds=1.0)
    assert read(rec) is None


def _record(eng, reqs, et):
    """The traced requests as a run whose tokens all came in a 1-s
    stretch."""
    served = [Served(Planned(0.0, r.prompt, r.max_new_tokens), 0.0, r,
                     stamps=[0.5] * len(r.output)) for r in reqs]
    return Record(run=Run(1.0, served, served, [], 1.0, 0.0),
                  model=dataclasses.asdict(eng.cfg), setup_s=0.0,
                  device_kind="cpu", seconds=1.0, engine=et)


def test_host_syncs_per_tick_reads_the_decode_spans(traced):
    eng, reqs, _, _, et = traced
    read = spec.metric_reader("host_syncs_per_tick")
    assert read(_record(eng, reqs, et)) == 1.0
    assert read(_record(eng, reqs, None)) is None
    # the CPU trace has no device: no decode program to read
    for name in ("decode_device_ms.mean", "decode_roofline"):
        assert spec.metric_reader(name)(_record(eng, reqs, et)) is None


def test_decode_roofline_against_a_hand_count(traced, monkeypatch):
    eng, reqs, _, _, et = traced
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"hbm_bytes_per_s": 1e9})
    # two executions of the decode step on the device, 4 and 8 ms
    et = dataclasses.replace(et, programs={"jit_decode_step": [4e-3, 8e-3],
                                           "jit_prefill": [1.0]})
    rec = _record(eng, reqs, et)
    assert spec.metric_reader("decode_device_ms.mean")(rec) == \
        pytest.approx(6.0)
    # weights, smoke llama3.2-1b in bfloat16: per layer q and o 64·64,
    # k and v 64·16, the MLP 3·64·256; two layers and the tied head
    # 64·256 over the vocabulary
    weights = 2 * (2 * (2 * 64 * 64 + 2 * 64 * 16 + 3 * 64 * 256)
                   + 64 * 256)
    assert flops.weight_bytes(rec.model) == weights == 270336
    # K and V of a position: 2 layers × 2 × 2 heads × 8 × 2 bytes, as
    # the engine's cache holds them
    per_key = 2 * 2 * 2 * 8 * 2
    assert sum(x.nbytes for x in jax.tree.leaves(eng.cache)) == \
        flops.kv_bytes(rec.model, eng.max_batch * eng.max_len) \
        == per_key * eng.max_batch * eng.max_len
    # prompts 5, 20, 3 with 3, 4, 2 tokens: decoded token j sees n + j
    # positions, over the 3 decode steps
    keys = (5 + 1) + (5 + 2) + (20 + 1) + (20 + 2) + (20 + 3) + (3 + 1)
    assert len(et.named("engine.decode")) == 3
    want = 100 * (weights + per_key * keys / 3) / (6e-3 * 1e9)
    got = spec.metric_reader("decode_roofline")(rec)
    assert got == pytest.approx(want, rel=1e-12)
    assert 0 < got <= 100

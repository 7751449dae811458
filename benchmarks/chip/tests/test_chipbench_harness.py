"""The harness's arithmetic on the CPU: the drivers against a fake engine
with a scripted clock, the metrics taken from what they record, the FLOP
count against a hand count, and discovery of files by name."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import arrivals, flops, spec  # noqa: E402
from benchmarks.chip.arrivals import Planned  # noqa: E402
from benchmarks.chip.driver import run_closed, run_open  # noqa: E402
from benchmarks.chip.record import Record  # noqa: E402

HERE = ROOT / "benchmarks" / "chip"


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """Two slots; a tick takes ``tick_s`` plus ``admit_s`` per admission;
    an admitted request gets its first token in that tick and one more
    token in every tick after (as ``ServingEngine.tick`` does)."""

    def __init__(self, clock, slots=2, tick_s=0.01, admit_s=0.05):
        self.clock, self.queue = clock, []
        self.active = [None] * slots
        self.tick_s, self.admit_s = tick_s, admit_s
        self.ticks = 0

    @property
    def load(self):
        return len(self.queue) + sum(r is not None for r in self.active)

    def submit(self, req):
        self.queue.append(req)
        return req

    def tick(self):
        self.ticks += 1
        for i, r in enumerate(self.active):
            if r is None and self.queue:
                r = self.active[i] = self.queue.pop(0)
                r.output.append(1)
                self.clock.t += self.admit_s
        self.clock.t += self.tick_s
        for i, r in enumerate(self.active):
            if r is None:
                continue
            r.output.append(2)
            if len(r.output) >= r.max_new_tokens:
                r.done_at = self.clock.t
                self.active[i] = None


class FakeScaler:
    def __init__(self, clock, cost=0.001):
        self.clock, self.cost, self.calls = clock, cost, []

    def target(self, queued, active):
        self.calls.append((queued, active))
        self.clock.t += self.cost
        return 1


def plan(dues, n_new=3):
    return [Planned(d, [5] * 4, n_new) for d in dues]


def test_open_loop_times_from_the_due_time():
    clock = Clock()
    eng, sc = FakeEngine(clock), FakeScaler(clock)
    run = run_open(eng, sc, plan([0.0, 0.0, 0.0, 0.5, 5.0]), 1.0,
                   drain_s=10.0, clock=clock, sleep=clock.sleep)
    assert len(run.window) == 4 and len(sc.calls) == eng.ticks
    a, b, c, d = run.window
    # a and b admitted in the first tick: 0.001 governor + 2 × 0.05 + 0.01
    assert a.stamps[0] == pytest.approx(0.111)
    assert a.ttft == b.ttft == pytest.approx(0.111)
    # first token and the tick's decode token come together: a gap of 0
    assert a.stamps[1] - a.stamps[0] == 0
    # c waits for a free slot: its time to first token counts the wait
    assert c.ttft > a.stamps[-1]
    # d is due at 0.5 while the engine is idle: the driver sleeps to it
    assert d.submit == pytest.approx(0.5)
    rec = Record(run=run, model={}, setup_s=1.0, device_kind="x",
                 seconds=1.0)
    assert rec.ttfts() == [s.ttft for s in run.window]
    gaps = rec.gaps()
    assert len(gaps) == 4 * 2 and min(gaps) == 0
    assert spec.metric_reader("gen_lag_ms.p99")(rec) >= 0
    # tokens counted in the window only
    n = sum(1 for s in run.served for t in s.stamps if t < 1.0)
    assert spec.metric_reader("tokens_per_s")(rec) == n / 1.0
    # seen over a shorter stretch, as a traced run is
    part = Record(run=run, model={}, setup_s=1.0, device_kind="x",
                  seconds=0.3)
    assert part.window == run.window[:3]


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (HERE / "metrics").glob("*.code.py")))
def test_a_cells_copy_of_a_metric_reads_as_the_metric(name):
    clock = Clock()
    eng, sc = FakeEngine(clock), FakeScaler(clock)
    run = run_open(eng, sc, plan([0.0, 0.0, 0.125, 0.25, 0.5]), 1.0,
                   drain_s=10.0, clock=clock, sleep=clock.sleep)
    rec = Record(run=run, model={}, setup_s=1.0, device_kind="x",
                 seconds=1.0, prefills=[(0.05, 0.04), (0.2, 0.06)])
    copy = spec.metric_reader(name)(rec)
    assert copy is not None
    assert copy == spec.metric_reader(name.removesuffix(".code"))(rec)


def test_each_cell_reports_what_its_per_layer_metrics_move():
    bench = spec.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = spec.Cell(bench, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_marks_fire_once_in_time_order():
    clock = Clock()
    seen = []
    run_open(FakeEngine(clock), FakeScaler(clock), plan([0.0, 0.2, 0.4]),
             0.5, drain_s=5.0, clock=clock, sleep=clock.sleep,
             marks=[(0.3, lambda: seen.append(("b", clock.t - 100))),
                    (0.0, lambda: seen.append(("a", clock.t - 100)))])
    assert [n for n, _ in seen] == ["a", "b"]
    assert seen[1][1] >= 0.3


def test_generator_lateness_is_submit_less_due():
    clock = Clock()
    eng = FakeEngine(clock, slots=1, tick_s=0.3, admit_s=0.0)
    run = run_open(eng, FakeScaler(clock), plan([0.0, 0.1]), 1.0,
                   drain_s=10.0, clock=clock, sleep=clock.sleep)
    late = run.window[1]
    # due at 0.1 but the driver was inside a 0.3 s tick
    assert late.submit - late.due == pytest.approx(0.201)
    rec = Record(run=run, model={}, setup_s=0.0, device_kind="x",
                 seconds=1.0)
    assert spec.metric_reader("gen_lag_ms.p99")(rec) == pytest.approx(
        0.201e3 * 0.99, rel=1e-6)


def test_unfinished_requests_are_failures_and_wait_until_the_end():
    clock = Clock()
    eng = FakeEngine(clock, slots=1, tick_s=0.5, admit_s=0.0)
    run = run_open(eng, FakeScaler(clock, 0.0), plan([0.0] * 6, 2), 0.5,
                   drain_s=1.0, clock=clock, sleep=clock.sleep)
    unserved = [s for s in run.window if not s.stamps]
    assert unserved and run.end >= 1.5
    rec = Record(run=run, model={}, setup_s=0.0, device_kind="x",
                 seconds=0.5)
    waited = [t for s, t in zip(run.window, rec.ttfts()) if not s.stamps]
    assert waited == [pytest.approx(run.end - s.due) for s in unserved]


def test_closed_loop_keeps_its_clients_busy():
    clock = Clock()
    eng, sc = FakeEngine(clock, slots=8), FakeScaler(clock)
    mix = {"loop": "closed", "prompt_len": {"dist": "uniform", "min": 4,
                                            "max": 8},
           "output_len": {"dist": "uniform", "min": 2, "max": 4}}
    p = arrivals.plan_closed(mix, 3, 200, 50)
    seen = []
    orig = eng.tick

    def tick():
        seen.append(eng.load)
        orig()

    eng.tick = tick
    run = run_closed(eng, sc, p, 1.0, clients=3, drain_s=5.0, clock=clock)
    assert max(seen) == 3 and seen[0] == 3
    assert {s.plan.client for s in run.served} == {0, 1, 2}
    # a caller's next request is due when its previous one finished
    by = {}
    for s in run.served:
        if s.plan.client in by:
            assert s.due == by[s.plan.client].stamps[-1]
        by[s.plan.client] = s
    assert all(s.req.done for s in run.window)


def test_open_plan_same_work_for_every_seed():
    mix = json.loads((HERE / "traffic" / "code-open.json").read_text())
    a, na = arrivals.plan_open(mix, 1, 30.0, 100)
    b, nb = arrivals.plan_open(mix, 2**40 + 9, 30.0, 100)
    assert na == nb == int(mix["arrivals"]["rate"] * 30 + 1e-9)
    # a Poisson stream: the same sizes at the same times, other tokens
    assert [(p.due, len(p.prompt), p.max_new) for p in a] == \
        [(p.due, len(p.prompt), p.max_new) for p in b]
    assert a[0].prompt != b[0].prompt
    assert sorted(len(p.prompt) for p in a[:na]) == \
        sorted(arrivals.length_quantiles(mix["prompt_len"], na))
    assert all(0 <= p.due < 30 for p in a[:na])
    assert all(p.due >= 30 for p in a[na:])
    assert min(len(p.prompt) for p in a) >= mix["prompt_len"]["min"]
    assert max(len(p.prompt) for p in a) <= mix["prompt_len"]["max"]


def test_bursts_hold_the_same_sizes_for_every_seed():
    for name, size in (("chat-burst", 32),):
        mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
        a, n = arrivals.plan_open(mix, 5, 50.0, 100)
        b, _ = arrivals.plan_open(mix, 6, 50.0, 100)
        assert [p.due for p in a] == [p.due for p in b]
        for i in range(0, n, size):
            sa, sb = a[i:min(i + size, n)], b[i:min(i + size, n)]
            assert sorted(p.max_new for p in sa) == \
                sorted(p.max_new for p in sb)
            assert sorted(len(p.prompt) for p in sa) == \
                sorted(len(p.prompt) for p in sb)
        # shuffled inside the bursts by the seed
        assert [p.max_new for p in a] != [p.max_new for p in b]


def test_burst_plan_mean_rate():
    mix = json.loads((HERE / "traffic" / "chat-burst.json").read_text())
    p, n = arrivals.plan_open(mix, 4, 40.0, 100)
    rate = arrivals.mean_rate(mix["arrivals"])
    assert n % 32 == 0 and abs(n - rate * 40) <= 32
    starts = [x.due for i, x in enumerate(p[:n]) if i % 32 == 0]
    assert starts[0] == 0.0 and len(starts) == n // 32


def test_flops_against_a_hand_count():
    m = json.loads((HERE / "configs" / "dsc33b-8l.json").read_text())["model"]
    # one layer by hand: q 7168·7168, k and v 7168·1024 each, o 7168·7168,
    # MLP 3·7168·19200; two FLOPs a multiply-add
    per_layer = 2 * (7168 * 7168 * 2 + 7168 * 1024 * 2 + 3 * 7168 * 19200)
    assert flops.dense_flops(m) == 8 * per_layer
    assert flops.head_flops(m) == 2 * 7168 * 32256
    # a decode token seeing 100 keys: 56 heads × 128 × 100 × 4 a layer
    assert flops.decode_flops(m, 100) == \
        8 * per_layer + 8 * 56 * 128 * 100 * 4 + 2 * 7168 * 32256
    # a 3-token prompt sees 1 + 2 + 3 keys, and the head once
    assert flops.prefill_flops(m, 3) == \
        3 * 8 * per_layer + 8 * 56 * 128 * 6 * 4 + 2 * 7168 * 32256


def test_window_flops_count_the_tokens_in_the_window():
    m = {"n_layers": 1, "d_model": 2, "n_heads": 1, "kv_heads": 1,
         "head_dim": 2, "d_ff": 2, "vocab": 3}
    clock = Clock()
    run = run_open(FakeEngine(clock, slots=1, tick_s=0.3, admit_s=0.0),
                   FakeScaler(clock, 0.0), plan([0.0], 5), 0.7,
                   drain_s=5.0, clock=clock, sleep=clock.sleep)
    s = run.window[0]
    assert s.stamps[:3] == [pytest.approx(0.3)] * 2 + [pytest.approx(0.6)]
    want = flops.prefill_flops(m, 4) + flops.decode_flops(m, 5) \
        + flops.decode_flops(m, 6)
    assert flops.window_flops(run.served, 0.7, m) == want
    assert flops.window_flops(run.served, 0.5, m) == \
        flops.prefill_flops(m, 4) + flops.decode_flops(m, 5)


def test_new_files_are_found_by_name(tmp_path):
    """A new configuration, mix and metric are picked up from their files
    and BENCHMARK.json entries, with no edit to any file."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "cfg.json").write_text(json.dumps({"model": {"a": 1}}))
    (tmp_path / "traffic" / "mix.json").write_text(json.dumps({"loop": "x"}))
    (tmp_path / "metrics" / "new_ms.p50.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench = {"configs": [{"name": "c", "file": "cfg.json"}],
             "workloads": [{"name": "c.mix", "config": "c",
                            "traffic": "mix", "chips": 1}],
             "end_to_end": [{"name": "x"}],
             "per_layer": [{"name": "new_ms.p50"},
                           {"name": "other", "workloads": ["z"]}]}
    cell = spec.Cell(bench, "c.mix", root=tmp_path, here=tmp_path)
    assert cell.config == {"model": {"a": 1}}
    assert cell.traffic == {"loop": "x"}
    assert [m["name"] for m in cell.per_layer] == ["new_ms.p50"]
    read = spec.metric_reader("new_ms.p50", tmp_path / "metrics")
    assert read(None) == 42.0


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = spec.load_benchmark(ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.Cell(bench, w["name"])
        assert arrivals.longest(cell.traffic) < cell.config["engine"]["max_len"]

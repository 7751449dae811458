"""A configuration brings its own reference and counts by new files
alone (``spec``): a test-only configuration under ``data/`` names a
reference and a counts module there, and a whole run on the CPU is judged
and counted by them.  Without them the harness judges and counts as
``reference.py`` and ``flops.py`` do, and the weights are drawn as they
were for the two served configurations."""

import json
import math
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from benchmarks.chip import correct, flops, harness, peaks, reference, \
    spec, weights  # noqa: E402
from benchmarks.chip.arrivals import Planned  # noqa: E402
from benchmarks.chip.driver import Run, Served  # noqa: E402
from benchmarks.chip.record import Record  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import ModelConfig, init_params  # noqa: E402
from repro.serving import Request  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = ROOT / "benchmarks" / "chip" / "configs"


@pytest.fixture
def bench(monkeypatch, tmp_path):
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12,
                                             "hbm_bytes_per_s": 1e9})
    # set after JAX read its configuration, this keeps the harness from
    # pointing the persistent cache anywhere: nothing is written
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield json.loads((DATA / "BENCHMARK.json").read_text())
    for k, v in keep.items():
        jax.config.update(k, v)


def records(monkeypatch) -> list:
    """Every ``Record`` the harness makes from now on."""
    made = []

    def record(**kw):
        made.append(Record(**kw))
        return made[-1]

    monkeypatch.setattr(harness, "Record", record)
    return made


def test_modules_are_found_by_the_names_in_the_configuration(bench):
    toy = spec.Cell(bench, "toy.open", root=DATA, here=DATA)
    assert toy.reference.__file__ == str(DATA / "toy_reference.py")
    assert toy.counts.__file__ == str(DATA / "toy_counts.py")
    # loaded once: a second cell gets the same modules
    again = spec.Cell(bench, "toy.open", root=DATA, here=DATA)
    assert again.reference is toy.reference and again.counts is toy.counts
    tiny = spec.Cell(bench, "tiny.open", root=DATA, here=DATA)
    assert tiny.reference is reference and tiny.counts is flops
    with pytest.raises(FileNotFoundError, match="no module"):
        spec.module("no_such_module", DATA)


@pytest.mark.parametrize("control", [False, True])
def test_a_run_is_judged_and_counted_by_the_configurations_modules(
        bench, monkeypatch, control):
    cell = spec.Cell(bench, "toy.open", root=DATA, here=DATA)
    calls = cell.reference.CALLS
    calls.clear()
    made = records(monkeypatch)
    r = harness.run_cell(cell, 2**33 + 5, 2.0, True, time.perf_counter(),
                         require_tpu=False, control=control)
    # every reference call was this module's, over the sample's tokens;
    # with the control, its float8 path too, and the run is not correct
    assert sum(rows for _, rows, q in calls if not q) == \
        r["info"]["sample_tokens"] > 0
    assert {q for _, _, q in calls} == {False, control}
    assert r["correct"] is (not control)
    # mfu by this module's FLOPs: 1e6 a prompt token, 1e3 a key decoded
    (rec,) = made
    assert rec.counts is cell.counts
    n = sum(1e6 * len(s.plan.prompt) if j == 0 else
            1e3 * (len(s.plan.prompt) + j)
            for s in rec.run.served for j, t in enumerate(s.stamps)
            if t < rec.seconds)
    assert r["metrics"]["mfu"]["value"] == pytest.approx(
        100 * n / (rec.seconds * 1e12), rel=1e-12)
    assert r["metrics"]["host_syncs_per_tick"]["value"] == 1.0


def test_decode_roofline_reads_the_configurations_bytes(bench):
    """A latent cache of 512 + 64 values a position a layer, and 2 MB of
    weights, from the test-only counts module."""
    cell = spec.Cell(bench, "toy.open", root=DATA, here=DATA)
    from benchmarks.chip.spans import EngineSpan, EngineTrace

    req = Request(prompt=[1] * 10, max_new_tokens=3)
    req.output = [1, 2, 3]
    s = Served(Planned(0.0, req.prompt, 3), 0.0, req, stamps=[0.1] * 3)
    et = EngineTrace([EngineSpan("engine.decode", 0.0, 0.1, {"syncs": 1})
                      for _ in range(2)], {}, {"jit_decode_step": [0.004]})
    rec = Record(run=Run(1.0, [s], [s], []), model=cell.config["model"],
                 setup_s=0.0, device_kind="cpu", seconds=1.0, engine=et,
                 counts=cell.counts)
    kv = 2 * (512 + 64) * 2 * ((10 + 1) + (10 + 2))
    assert spec.metric_reader("decode_roofline")(rec) == pytest.approx(
        100 * (2e6 + kv / 2) / (4e-3 * 1e9), rel=1e-12)


def _old_std(names, shape, stacked):
    """The rule the weights were drawn by before stacks of matrices got
    their fan-in."""
    name = names[-1]
    core = shape[1:] if stacked else shape
    if name == "embed":
        return 1.0 / math.sqrt(core[-1])
    if len(core) == 2:
        return 1.0 / math.sqrt(core[0])
    if name in ("bq", "bk", "bv"):
        return 0.5
    return 0.1


def _leaves(cfg):
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [str(p.key) for p in path
                 if isinstance(p, jax.tree_util.DictKey)]
        yield names, leaf.shape, "blocks" in names


@pytest.mark.parametrize("name", ["dsc33b-8l", "internvl2-1b"])
def test_served_configurations_draw_as_before(name):
    """At full width, every leaf of both served configurations gets the
    scale it got before."""
    model = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    leaves = list(_leaves(ModelConfig(**model)))
    assert len(leaves) >= 10
    for names, shape, stacked in leaves:
        assert weights._std(names, shape, stacked) == \
            _old_std(names, shape, stacked), (names, shape)


def test_stacked_experts_get_their_fan_in():
    cfg = get_smoke_config("mixtral-8x22b")
    d, f = cfg.d_model, cfg.d_ff
    experts = {n[-1]: (shape, weights._std(n, shape, stacked))
               for n, shape, stacked in _leaves(cfg) if "moe" in n}
    assert experts["w1"] == ((cfg.n_layers, cfg.n_experts, d, f),
                             1 / math.sqrt(d))
    assert experts["w3"][1] == 1 / math.sqrt(d)
    assert experts["w2"] == ((cfg.n_layers, cfg.n_experts, f, d),
                             1 / math.sqrt(f))
    # as drawn: an expert's output keeps the scale of its input
    w1 = np.asarray(weights.make_params(cfg, 3)["blocks"][0]["moe"]["w1"],
                    np.float32)
    assert w1.std() == pytest.approx(1 / math.sqrt(d), rel=0.05)


def test_default_path_judges_and_counts_as_reference_and_flops(bench):
    """Without a ``reference`` or ``counts`` in its file, a cell's gaps
    and FLOPs are those of direct calls to ``reference.py`` and
    ``flops.py``."""
    cell = spec.Cell(bench, "tiny.open", root=DATA, here=DATA)
    model = cell.config["model"]
    params = weights.make_params(ModelConfig(**model), 11)
    rng = np.random.default_rng(0)
    chosen = []
    for n, m in ((9, 5), (30, 7)):
        req = Request(prompt=rng.integers(0, 256, n).tolist(),
                      max_new_tokens=m)
        req.output = rng.integers(0, 256, m).tolist()
        chosen.append(Served(Planned(0.0, req.prompt, m), 0.0, req,
                             stamps=[0.1 * (j + 1) for j in range(m)]))
    got = correct.gaps(cell.reference, params, model, chosen, control=True)
    a = reference.Arch(model)
    want = {"served": [], "control": []}
    for s in chosen:
        n, out = len(s.plan.prompt), np.asarray(s.req.output)
        toks = list(s.plan.prompt) + out[:-1].tolist()
        rows = list(range(n - 1, n - 1 + len(out)))
        ref = reference.logits_at(params, toks, rows, a)
        ctl = reference.logits_at(params, toks, rows, a, quant=True)
        best = ref.max(-1)
        idx = np.arange(len(out))
        want["served"].append(best - ref[idx, out])
        want["control"].append(best - ref[idx, ctl.argmax(-1)])
    assert got["tokens"] == 12
    for k, v in want.items():
        assert got[k]["widest"] == max(float(g.max()) for g in v)
        assert got[k]["flips"] == sum(int((g > 0).sum()) for g in v)
        assert got[k]["mean"] == sum(float(g.sum()) for g in v) / 12
    rec = Record(run=Run(0.5, chosen, chosen, []), model=model, setup_s=0.0,
                 device_kind="cpu", seconds=0.5, counts=cell.counts)
    # tokens stamped before 0.5 s: each prompt's first and three more
    f = flops.window_flops(chosen, 0.5, model)
    assert f == pytest.approx(
        flops.prefill_flops(model, 9) + flops.prefill_flops(model, 30)
        + sum(flops.decode_flops(model, n + j)
              for n in (9, 30) for j in range(1, 4)), rel=1e-12)
    assert spec.metric_reader("mfu")(rec) == 100 * f / (0.5 * 1e12)

"""A whole run on the CPU at a test size, with the look for a chip
skipped: a sound run comes out correct, and the timed path broken
underneath makes ``correct`` false, once for each fault a serving cell
can have.  Also the refusals of ``run.py``."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import harness, peaks, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12})
    # set after JAX read its configuration, this keeps the harness from
    # pointing the persistent cache anywhere: nothing is written
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    bench = json.loads((DATA / "BENCHMARK.json").read_text())
    yield spec.Cell(bench, "tiny.open", root=DATA, here=DATA)
    for k, v in keep.items():
        jax.config.update(k, v)


def run(cell, trace=False):
    return harness.run_cell(cell, 2**33 + 17, 2.0, trace,
                            time.perf_counter(), require_tpu=False)


def broken(monkeypatch, wrap):
    """Build the engine as the harness does, then break it with ``wrap``."""
    build = harness.build

    def build_broken(cell, seed):
        params, engine, scaler = build(cell, seed)
        wrap(engine)
        return params, engine, scaler

    monkeypatch.setattr(harness, "build", build_broken)


def test_sound_run_is_correct(tiny):
    r = run(tiny)
    assert r["correct"] is True
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["info"]["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "ttft_p90_ms", "itl_p50_ms",
                                 "itl_p99_ms", "setup_s"}


def test_traced_run_reports_the_per_layer_metrics(tiny):
    r = run(tiny, trace=True)
    assert r["correct"] is True and r["failed"] == 0
    # the CPU trace has no device plane: the device metric is left out
    assert set(r["metrics"]) == {"gen_lag_ms.p99", "governor_ms_per_tick",
                                 "prefill_ms.mean", "tick_ms.decode_only",
                                 "tick_ms.admit", "mfu"}


def alter_tokens(engine):
    tick = engine.tick

    def bad():
        n = tick()
        for r in engine.active:
            if r is not None and len(r.output) % 3 == 0:
                r.output[-1] = (r.output[-1] + 1) % engine.cfg.vocab
        return n

    engine.tick = bad


def state_unchanged(engine):
    decode = engine._decode
    engine._decode = lambda p, t, pos, c: (decode(p, t, pos, c)[0], c)


def half_batch(engine):
    """Every other slot left out of the decode step (its logits zero)."""
    decode = engine._decode

    def bad(p, t, pos, c):
        logits, c2 = decode(p, t, pos, c)
        return logits.at[1::2].set(jnp.zeros_like(logits[1::2])), c2

    engine._decode = bad


@pytest.mark.parametrize("fault", [alter_tokens, state_unchanged,
                                   half_batch])
def test_fault_is_not_correct(tiny, monkeypatch, fault):
    broken(monkeypatch, fault)
    r = run(tiny)
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_control_is_not_correct(tiny):
    """The float8 control, in the program's place at the positions of the
    sound run's tokens, comes out not correct through the same checks,
    where the sound run's tokens keep every limit."""
    r = harness.run_cell(tiny, 99, 2.0, False, time.perf_counter(),
                         require_tpu=False, control=True)
    limits = tiny.config["correct"]
    served = r["info"]["gaps"]["served"]
    assert served["widest"] <= limits["max_logit_gap"]
    assert served["mean"] <= limits["mean_logit_gap"]
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > limits["max_logit_gap"]
    assert r["checks"]["mean_logit_gap"]["value"] > limits["mean_logit_gap"]


def test_int8_cache_is_not_correct(tiny):
    """The program's own int8 KV cache, the precision below the stated
    bfloat16 cache, comes out not correct."""
    tiny.config["model"]["cache_dtype"] = "int8"
    r = run(tiny)
    assert r["correct"] is False
    assert r["checks"]["mean_logit_gap"]["value"] > \
        r["checks"]["mean_logit_gap"]["limit"]


def drop_one_request(engine):
    """The window's second request is taken and never served (warm-up
    prompts are all ones)."""
    submit = engine.submit
    seen = []

    def bad(req):
        if any(t != 1 for t in req.prompt):
            seen.append(req)
        return req if len(seen) == 2 and seen[-1] is req else submit(req)

    engine.submit = bad


def test_unanswered_request_is_not_correct(tiny, monkeypatch):
    broken(monkeypatch, drop_one_request)
    r = run(tiny)
    assert r["correct"] is False and r["failed"] == 1
    assert r["checks"]["unfinished"]["value"] == 1


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_without_a_chip():
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dsc33b-8l.code-open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dsc33b-8l.code-open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""

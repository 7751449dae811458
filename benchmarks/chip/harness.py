"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up makes the weights on the device from the seed, builds the
``ServingEngine`` and the ``prediction`` ``AutoScaler`` from the cell's
configuration file, plans the traffic from its mix file, and warms every
program the window will run: one admission in each prefill bucket the
plan reaches, and the decode step.  The window then drives the engine as
an open or closed loop (``driver``).  After it the engine's state is
freed and the float32 reference judges a sample of what was served
(``correct``).

The last line of standard output is one JSON object; the numbers
compared, with their limits, close standard error and that object.
``--trace 1`` records a profiler trace of the whole window and drain, and
reports the per-layer metrics over the window's first ``TRACE_S`` seconds
instead of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import jax

from repro.core.events import EventKind
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ModelConfig
from repro.serving import AutoScaler, ServingEngine

from . import arrivals, correct, trace as tr
from .driver import drain, run_closed, run_open
from .record import Record
from .spec import ROOT, Cell, load_benchmark, metric_reader
from .weights import make_params

__all__ = ["ChipMissing", "build", "main", "run_cell"]


class ChipMissing(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def devices_for(cell: Cell, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise ChipMissing(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:cell.chips]


def setup_cache() -> None:
    """Persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program, however
    quickly it compiled, so that a warm start compiles nothing."""
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build(cell: Cell, seed: int):
    """Weights, engine and autoscaler of ``cell``."""
    model = dict(cell.config["model"])
    cfg = ModelConfig(**model)
    params = make_params(cfg, seed)
    eng = cell.config["engine"]
    engine = ServingEngine(cfg, params, max_batch=int(eng["max_batch"]),
                           max_len=int(eng["max_len"]))
    scaler = AutoScaler(engine.monitor, max_replicas=engine.max_batch,
                        policy="prediction", bus=engine.bus)
    return params, engine, scaler


def plan_for(cell: Cell, seed: int, seconds: float, engine) -> tuple:
    mix = cell.traffic
    vocab = int(cell.config["model"]["vocab"])
    if arrivals.longest(mix) >= engine.max_len:
        raise ValueError(f"mix {cell.entry['traffic']} reaches "
                         f"{arrivals.longest(mix)} positions; the engine "
                         f"holds {engine.max_len}")
    if mix["loop"] == "open":
        return arrivals.plan_open(mix, seed, seconds, vocab)
    # a closed loop sends at most one request per caller per tick
    n = int(mix["clients"]) * max(8, math.ceil(seconds))
    return arrivals.plan_closed(mix, seed, n, vocab), None


def warm(engine, scaler, plan, annotate) -> None:
    """Serve one short request in each prefill bucket of ``plan``; this
    compiles the prefills, the admission's eager programs and the decode
    step (4 tokens, so that the per-slot position read runs too)."""
    bs = sorted({arrivals.prefill_bucket(len(p.prompt)) for p in plan})
    warm_plan = [arrivals.Planned(0.0, [1] * (b - 1), 4) for b in bs]
    drain(engine, scaler, warm_plan, clock=time.perf_counter,
          annotate=annotate)


#: a traced run reports its per-layer metrics over this much of the
#: window from its opening: enough for a steady stretch and a whole
#: burst period
TRACE_S = 15.0


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             control: bool = False) -> dict:
    """One run.  Returns the result object (its last key, ``checks``,
    holds each number compared with its limit).  With ``control`` the
    float8 control takes the program's place in the comparison, and a
    sound benchmark reports the run not correct."""
    devs = devices_for(cell, require_tpu)
    setup_cache()
    compiles = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    params, engine, scaler = build(cell, seed)
    plan, _ = plan_for(cell, seed, seconds, engine)
    warm(engine, scaler, plan, _annotate)
    prefills: list = []

    def on_done(ev):
        if ev.type_name == "prefill":
            prefills.append((ev.time, ev.elapsed))

    engine.bus.subscribe(on_done, kinds=[EventKind.TASK_COMPLETED])
    tmp = None
    stretch = min(seconds, TRACE_S) if trace else seconds
    if trace:
        # the profiler runs until the drain has ended: stopping it takes
        # minutes on the chip, time that would otherwise fall in the loop
        tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        log_dir = Path(tmp.name)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)

    compiles_before = compiles[0]
    setup_s = time.perf_counter() - t_start
    mix = cell.traffic
    if mix["loop"] == "open":
        run = run_open(engine, scaler, plan, seconds,
                       drain_s=arrivals.DRAIN_S, clock=time.perf_counter,
                       sleep=time.sleep, annotate=_annotate)
    else:
        run = run_closed(engine, scaler, plan, seconds,
                         clients=int(mix["clients"]),
                         drain_s=arrivals.DRAIN_S, clock=time.perf_counter,
                         annotate=_annotate)
    compiles_in_window = compiles[0] - compiles_before
    jax.monitoring.unregister_event_duration_listener(on_event)
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = tr.reduce(*tr.read_xplane(tr.newest_xplane(log_dir)),
                            seconds=stretch)
        tmp.cleanup()
    rec = Record(run=run, model=cell.config["model"], setup_s=setup_s,
                 device_kind=devs[0].device_kind, seconds=stretch,
                 prefills=[(t - run.t0, e) for t, e in prefills],
                 trace=reduced)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state goes before the reference runs beside the weights
    engine.cache = engine.params = None
    del engine, scaler
    gc.collect()
    t_ref = time.perf_counter()
    limits = cell.config["correct"]
    chosen = correct.sample(run, seed, int(limits["sample_tokens"]))
    g = correct.gaps(params, cell.config["model"], chosen, control=control)
    t_ref = time.perf_counter() - t_ref
    checks = correct.checks(run, g, limits,
                            "control" if control else "served")
    ok = bool(chosen) and all(v <= lim for _, v, lim in checks)
    failed = sum(1 for s in run.window if not s.stamps)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {"correct": ok, "attempted": len(run.window), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["info"] = {"compiles_in_window": compiles_in_window,
                      "sample_requests": g["requests"],
                      "sample_tokens": g["tokens"],
                      "drain_end_s": run.end,
                      "reference_s": t_ref,
                      "requests_served": sum(1 for s in run.served
                                             if s.stamps)}
    result["info"]["gaps"] = {k: g[k] for k in g
                              if k in ("served", "control")}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    cell = Cell(load_benchmark(ROOT), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except ChipMissing as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


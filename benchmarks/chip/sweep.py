"""Find the highest rate a cell's engine sustains, on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \
        --rates r1 r2 ...

Builds the cell once, then offers its mix at each mean rate (requests per
second) in turn for one window, and prints one JSON line per rate: the
tokens per second completed, time to first token and gaps, and the
backlog when the window closed.  Between rates the engine is emptied.
The knee is the highest rate whose backlog does not grow through the
window; the cell's mix file then carries 0.8 of it as a number.  The
benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import arrivals  # noqa: E402
from benchmarks.chip.driver import run_open  # noqa: E402
from benchmarks.chip.harness import (build, devices_for, setup_cache,  # noqa: E402
                                     warm)
from benchmarks.chip.record import Record, pct  # noqa: E402
from benchmarks.chip.spec import Cell, load_benchmark, metric_reader  # noqa: E402


def with_rate(mix: dict, rate: float) -> dict:
    mix = copy.deepcopy(mix)
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        arr["rate"] = rate
    else:
        size = int(arr["burst_size"])
        arr["gap"] = size / rate - (size - 1) * float(arr["spacing"])
    return mix


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = Cell(load_benchmark(ROOT), args.workload)
    devs = devices_for(cell)
    setup_cache()
    params, engine, scaler = build(cell, args.seed)
    vocab = int(cell.config["model"]["vocab"])
    plan, _ = arrivals.plan_open(cell.traffic, args.seed, args.seconds, vocab)
    warm(engine, scaler, plan, lambda name: contextlib.nullcontext())
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in args.rates:
        mix = with_rate(cell.traffic, rate)
        plan, n_win = arrivals.plan_open(mix, args.seed, args.seconds, vocab)
        plan = plan[:n_win]
        backlog = {}

        def on_close():
            backlog["queued"] = len(engine.queue)
            backlog["active"] = sum(r is not None for r in engine.active)

        run = run_open(engine, scaler, plan, args.seconds, drain_s=30.0,
                       clock=time.perf_counter, sleep=time.sleep,
                       marks=[(args.seconds, on_close)])
        rec = Record(run=run, model=cell.config["model"], setup_s=0.0,
                     device_kind=devs[0].device_kind, seconds=args.seconds,
                     counts=cell.counts)
        out = {"rate": rate, "requests": n_win, **backlog,
               "drain_end_s": run.end,
               "unfinished": sum(1 for s in run.window
                                 if not (s.req and s.req.done))}
        for m in ("tokens_per_s", "ttft_p90_ms", "itl_p50_ms",
                  "itl_p99_ms", "gen_lag_ms.p99", "mfu"):
            out[m] = metric_reader(m)(rec)
        out["ttft_p50_ms"] = pct(rec.ttfts(), 50) * 1e3
        print(json.dumps(out), flush=True)
        engine.queue.clear()
        while engine.load:
            engine.tick()


if __name__ == "__main__":
    main()

"""Readings that set a configuration's ``correct`` limit, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--int8-cache]

In one process, for each seed, one run of the cell as ``run.py`` makes it
(at the cell's own load, with a shorter window), with the float8 control
in the program's place in the comparison (``correct``): the checks judge
the tokens that the float8 reference puts first at the same positions of
the same prompts and served tokens, and the run must come out not
correct.  Prints one JSON line per seed with the checks (the control's)
and the gaps of both, the program's (``served``) and the control's.
``--int8-cache`` runs the program with its own int8 KV cache, the
program's path in the precision below bfloat16, as the second control;
its checks judge the program's served tokens.  The benchmark's own runs
never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip.harness import run_cell  # noqa: E402
from benchmarks.chip.spec import Cell, load_benchmark  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--int8-cache", action="store_true")
    args = ap.parse_args()
    for seed in args.seeds:
        cell = Cell(load_benchmark(ROOT), args.workload)
        if args.int8_cache:
            cell.config["model"]["cache_dtype"] = "int8"
        t = time.perf_counter()
        r = run_cell(cell, seed, args.seconds, False, t,
                     control=not args.int8_cache)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "int8_cache": args.int8_cache,
                          "correct": r["correct"], "checks": r["checks"],
                          "info": r["info"], "metrics": r["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()

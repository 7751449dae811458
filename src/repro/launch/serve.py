"""Serving launcher — continuous batching + prediction autoscaling demo.

    python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 16 --policy prediction
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..core import policy_entry, registered_policies
from ..models import init_params
from ..serving import AutoScaler, Request, ServingEngine
from .compile_cache import enable_compile_cache


def draw_prompts(rng: np.random.Generator, n: int, vocab: int,
                 min_len: int, max_len: int) -> list[list[int]]:
    """``n`` prompts of uniform token ids, lengths uniform in
    ``[min_len, max_len]``."""
    return [rng.integers(0, vocab, size=rng.integers(min_len, max_len + 1))
            .tolist() for _ in range(n)]


def serve(engine: ServingEngine, scaler: AutoScaler,
          prompts: list[list[int]], max_new_tokens: int
          ) -> tuple[list[Request], int, list[int]]:
    """Submit every prompt, then tick the engine until it drains, asking
    the autoscaler for its target Δ before each tick.

    Returns (requests, ticks, Δ trace).
    """
    reqs = [engine.submit(Request(prompt=p, max_new_tokens=max_new_tokens))
            for p in prompts]
    targets = []
    while engine.load:
        targets.append(scaler.target(len(engine.queue),
                                     sum(r is not None
                                         for r in engine.active)))
        engine.tick()
    return reqs, len(targets), targets


def main() -> None:
    # Any registered non-sharing policy can drive the autoscaler —
    # new policies show up here without touching this launcher.
    policies = [p for p in registered_policies()
                if not policy_entry(p).sharing]
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 23),
                    metavar=("MIN", "MAX"),
                    help="prompt lengths are drawn uniformly from "
                         "[MIN, MAX]")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--policy", default="prediction", choices=policies)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           max_len=args.max_len)
    scaler = AutoScaler(engine.monitor, max_replicas=args.max_batch,
                        policy=args.policy, bus=engine.bus)
    prompts = draw_prompts(np.random.default_rng(args.seed), args.requests,
                           cfg.vocab, *args.prompt_len)
    t0 = time.perf_counter()
    reqs, _, targets = serve(engine, scaler, prompts, args.max_new)
    wall = time.perf_counter() - t0
    lat = [r.done_at - r.submitted_at for r in reqs]
    print(f"{args.requests} requests, {engine.tokens_out} tokens in "
          f"{wall:.2f}s ({engine.tokens_out / wall:.1f} tok/s)")
    print(f"latency p50={np.percentile(lat, 50)*1e3:.0f}ms "
          f"p95={np.percentile(lat, 95)*1e3:.0f}ms")
    print(f"autoscaler Δ trace (first 20): {targets[:20]}")


if __name__ == "__main__":
    main()

"""Bring-up smoke: serve llama3.2-1b at its full published width on one TPU.

    python chip_smoke.py [--seed N]

Runs from the root of a checkout with nothing prepared: it puts ``src`` on
``sys.path`` itself and makes the weights from ``--seed``.  The serving
path is the launcher's own (``repro.launch.serve.serve``): one
``ServingEngine`` (16 slots, 2048-token cache) ticked until 32 requests
drain, with the ``prediction`` autoscaler asked for its Δ before every
tick.  Prompt lengths are drawn from the seed over 64–1024 tokens, and
every request asks for 64 new tokens.

The requests are served twice.  The first pass compiles every program the
loop runs and is reported as compile and warm-up time; the second pass is
timed and checked:

* every request is done with 64 tokens, and ``engine.tokens_out`` is
  their sum;
* every logit the engine computed, in prefill and decode, is finite;
* the cached path agrees with the model's own full forward pass for the
  request with the longest prompt (see ``TOL_LOGIT``).  This is a
  self-consistency check, not a comparison with a float32 reference.

Without a TPU the script exits nonzero and prints no result.  Every phase
runs in this one process; a failed check or any exception ends it with a
nonzero code.  The last line of stdout is one JSON object naming the
device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import draw_prompts, serve  # noqa: E402
from repro.models import forward, init_params  # noqa: E402
from repro.serving import AutoScaler, ServingEngine  # noqa: E402

ARCH = "llama3.2-1b"
MAX_BATCH, MAX_LEN = 16, 2048
N_REQUESTS, PROMPT_LEN, MAX_NEW = 32, (64, 1024), 64
#: ``forward`` scans attention in 512-query chunks (``gqa_attention``), so
#: the reference sequence is right-padded to a multiple of this.
FORWARD_PAD = 512
#: How far below the full forward pass's maximum logit an emitted token's
#: logit may sit.  At random init the logits are about N(0, 1) (unit-RMS
#: final norm against a 1/√d embedding), so the maximum over 128k tokens
#: is near 4.4, where one bf16 step is 1/32.  The engine (bucketed prefill,
#: then one-token decode against the cache) and the full pass round bf16
#: activations in different orders through 16 layers, so a near-tie can
#: flip by a few steps: 0.25 is 8 of them.  A token that the cached path
#: got wrong sits several units below the maximum.
TOL_LOGIT = 0.25

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """XLA compilations and persistent-cache hits seen by ``jax.monitoring``
    since :meth:`register`."""

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def register(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits


class CheckFailed(RuntimeError):
    """A result the smoke checks came out wrong."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def _watch_finite(engine: ServingEngine) -> list[jax.Array]:
    """Wrap the engine's jitted prefill and decode steps so that each call
    also records, on the device, whether every logit it returned is
    finite.  The flags are read once, after the loop."""
    flags: list[jax.Array] = []
    all_finite = jax.jit(lambda x: jnp.isfinite(x).all())

    def wrap(step):
        def run(*args):
            logits, cache = step(*args)
            flags.append(all_finite(logits))
            return logits, cache
        return run

    engine._prefill = wrap(engine._prefill)
    engine._decode = wrap(engine._decode)
    return flags


def _forward_gaps(cfg, params, req) -> np.ndarray:
    """For each token ``req`` emitted, how far its logit sits below the
    maximum logit at that position in the full forward pass."""
    seq = req.prompt + req.output
    padded = -(-len(seq) // FORWARD_PAD) * FORWARD_PAD
    toks = jnp.asarray([seq + [0] * (padded - len(seq))], jnp.int32)
    first = len(req.prompt) - 1          # position that predicts output[0]
    emitted = jnp.asarray(req.output, jnp.int32)

    @jax.jit
    def gaps(params, toks):
        logits, _ = forward(params, toks, cfg)
        rows = jax.lax.dynamic_slice_in_dim(
            logits[0, :, :cfg.vocab], first, len(req.output))
        rows = rows.astype(jnp.float32)
        picked = jnp.take_along_axis(rows, emitted[:, None], axis=1)[:, 0]
        return rows.max(axis=1) - picked, jnp.isfinite(logits).all()

    gap, finite = gaps(params, toks)
    _check(bool(finite), "full forward pass produced non-finite logits")
    return np.asarray(gap)


def smoke(cfg, *, seed: int, max_batch: int, max_len: int, n_requests: int,
          prompt_len: tuple[int, int], max_new: int,
          counter: CompileCounter) -> None:
    """Serve ``n_requests`` seeded prompts twice through the launcher's
    loop, print what it took, and check the second pass's results."""
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} params={n_params} param_bytes={n_bytes} "
          f"init_s={time.perf_counter() - t0:.3f}")

    engine = ServingEngine(cfg, params, max_batch=max_batch, max_len=max_len)
    scaler = AutoScaler(engine.monitor, max_replicas=max_batch,
                        policy="prediction", bus=engine.bus)
    finite_flags = _watch_finite(engine)
    prompts = draw_prompts(np.random.default_rng(seed), n_requests,
                           cfg.vocab, *prompt_len)
    lens = [len(p) for p in prompts]
    print(f"serving: max_batch={max_batch} max_len={max_len} "
          f"requests={n_requests} prompt_len min={min(lens)} "
          f"max={max(lens)} mean={np.mean(lens):.1f} max_new={max_new}")

    c0, s0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    _, warm_ticks, _ = serve(engine, scaler, prompts, max_new)
    warm_s = time.perf_counter() - t0
    c1, s1, h1 = counter.snapshot()
    print(f"compile+warm-up: {warm_s:.3f} s over {warm_ticks} ticks; "
          f"{c1 - c0} compiles ({s1 - s0:.3f} s), "
          f"{h1 - h0} persistent-cache hits")

    out0 = engine.tokens_out
    t0 = time.perf_counter()
    reqs, ticks, deltas = serve(engine, scaler, prompts, max_new)
    wall = time.perf_counter() - t0
    c2, s2, h2 = counter.snapshot()
    served = engine.tokens_out - out0
    print(f"drained loop: {wall:.3f} s, {ticks} ticks, {served} tokens "
          f"({served / wall:.1f} tok/s); {c2 - c1} compiles in the loop")
    print(f"autoscaler Δ trace (first 20): {deltas[:20]}")

    _check(all(r.done and len(r.output) == max_new for r in reqs),
           f"not every request finished with {max_new} tokens")
    _check(served == sum(len(r.output) for r in reqs),
           f"tokens_out grew by {served}, requests hold "
           f"{sum(len(r.output) for r in reqs)}")
    _check(bool(jnp.stack(finite_flags).all()),
           "the engine computed non-finite logits")
    probe = max(reqs, key=lambda r: len(r.prompt))
    gap = _forward_gaps(cfg, params, probe)
    print(f"consistency: request prompt_len={len(probe.prompt)} "
          f"tokens={len(gap)} exact_argmax={int((gap == 0).sum())} "
          f"max_gap={gap.max():.6f} tol={TOL_LOGIT}")
    _check(bool((gap <= TOL_LOGIT).all()),
           f"cached path disagrees with the full forward pass: a token "
           f"sits {gap.max():.4f} below the maximum logit")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Serve llama3.2-1b at full width on one TPU chip.")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    count = jax.device_count()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}")
    print(f"compile cache: {cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke runs only on the chip",
              file=sys.stderr)
        return 1
    counter = CompileCounter().register()
    smoke(get_config(ARCH), seed=args.seed, max_batch=MAX_BATCH,
          max_len=MAX_LEN, n_requests=N_REQUESTS, prompt_len=PROMPT_LEN,
          max_new=MAX_NEW, counter=counter)
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

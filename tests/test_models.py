"""Model substrate: per-arch smoke + decode/forward parity."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SHAPES, cell_runnable, get_config, \
    get_smoke_config
from repro.models import (decode_step, forward, init_params,
                          lm_loss, prefill)
from repro.serving import ServingEngine

KEY = jax.random.PRNGKey(0)

# Whole-module: per-arch forward/decode/train-step sweeps dominate the
# suite's wall clock (~2 min of the ~3.5); CI's fast lane skips them
# (-m "not slow"), the tests-full job still runs everything.
pytestmark = pytest.mark.slow


def _batch(cfg, B=2, S=32):
    F = cfg.frontend_len
    toks = jax.random.randint(KEY, (B, S - F), 0, cfg.vocab)
    prefix = (jax.random.normal(KEY, (B, F, cfg.d_model), jnp.bfloat16)
              if F else None)
    return toks, prefix


@pytest.mark.parametrize("arch", list(ARCH_IDS))
class TestArchSmoke:
    def test_forward_shapes_no_nans(self, arch):
        cfg = get_smoke_config(arch)
        params = init_params(KEY, cfg)
        toks, prefix = _batch(cfg)
        logits, aux = forward(params, toks, cfg, prefix=prefix)
        assert logits.shape == (2, 32, cfg.padded_vocab())
        assert not np.isnan(np.asarray(logits, np.float32)).any()
        assert float(aux) >= 0.0

    def test_train_step_decreases_loss(self, arch):
        from repro.optim import AdamWConfig, adamw_init
        from repro.train.steps import StepConfig, make_train_step
        cfg = get_smoke_config(arch)
        params = init_params(KEY, cfg)
        opt = AdamWConfig(lr=5e-3)
        step_fn = jax.jit(make_train_step(cfg, None, opt,
                                          StepConfig(accum=2, warmup=1)))
        opt_state = adamw_init(params, opt)
        toks, prefix = _batch(cfg, B=4)
        F = cfg.frontend_len
        labels = jnp.concatenate(
            [jnp.full((4, F), -1, jnp.int32), toks], axis=1) if F else toks
        batch = {"tokens": toks.reshape(2, 2, -1),
                 "labels": labels.reshape(2, 2, -1)}
        if prefix is not None:
            batch["prefix"] = prefix.reshape(2, 2, F, -1)
        losses = []
        for i in range(5):
            params, opt_state, m = step_fn(
                params, opt_state, jnp.asarray(i, jnp.int32), batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_long_500k_flags(self, arch):
        cfg = get_config(arch)
        ok, why = cell_runnable(cfg, SHAPES["long_500k"])
        expect = arch in ("mixtral-8x22b", "recurrentgemma-2b", "rwkv6-7b")
        assert ok == expect, (arch, why)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma2-9b",
                                  "mixtral-8x22b", "recurrentgemma-2b",
                                  "rwkv6-7b", "qwen1.5-110b"])
def test_decode_matches_forward(arch):
    """Prefill T tokens then decode the rest one-by-one: logits must
    match the full-sequence forward at every step — this pins the cache
    indexing, ring masking, RoPE positions and recurrent states.
    (capacity_factor is raised so MoE archs are dropless: capacity
    dropping legitimately differs between 24-token and 1-token calls.)"""
    cfg = get_smoke_config(arch).replace(frontend_len=0,
                                         capacity_factor=16.0)
    params = init_params(KEY, cfg)
    B, S, T = 2, 24, 16
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab)
    full_logits, _ = forward(params, toks, cfg)

    _, cache = prefill(params, toks[:, :T], cfg, max_len=S)
    for t in range(T, S):
        step_logits, cache = decode_step(
            params, toks[:, t], jnp.asarray(t, jnp.int32), cache, cfg)
        np.testing.assert_allclose(
            np.asarray(step_logits, np.float32),
            np.asarray(full_logits[:, t], np.float32),
            rtol=2e-2, atol=2e-2,
        )


@pytest.mark.parametrize("arch", ["llama3.2-1b", "gemma2-9b",
                                  "mixtral-8x22b", "recurrentgemma-2b",
                                  "rwkv6-7b", "qwen1.5-110b"])
def test_decode_matches_forward_at_mixed_depths(arch):
    """Continuous batching: each slot prefilled alone to its own depth,
    then decoded together with per-slot positions.  Every slot's logits
    must match the full forward at its own position.  The local layers'
    ring caches (window 16 in the smoke configs) wrap while decoding."""
    cfg = get_smoke_config(arch).replace(frontend_len=0,
                                         capacity_factor=16.0)
    params = init_params(KEY, cfg)
    S, depths = 32, (4, 9, 16)
    toks = jax.random.randint(jax.random.PRNGKey(7), (len(depths), S), 0,
                              cfg.vocab)
    full_logits, _ = forward(params, toks, cfg)

    caches = [prefill(params, toks[b:b + 1, :t], cfg, max_len=S)[1]
              for b, t in enumerate(depths)]
    cache = {"blocks": jax.tree.map(lambda *c: jnp.concatenate(c, 1),
                                    *[c["blocks"] for c in caches]),
             "rest": jax.tree.map(lambda *c: jnp.concatenate(c, 0),
                                  *[c["rest"] for c in caches])}
    step = jax.jit(functools.partial(decode_step, cfg=cfg))
    rows = np.arange(len(depths))
    for i in range(S - max(depths)):
        pos = np.asarray(depths) + i
        step_logits, cache = step(params, toks[rows, pos],
                                  jnp.asarray(pos, jnp.int32), cache)
        np.testing.assert_allclose(
            np.asarray(step_logits, np.float32),
            np.asarray(full_logits[rows, pos], np.float32),
            rtol=2e-2, atol=2e-2, err_msg=f"positions {pos}")


def _fused_roots(hlo: str) -> dict:
    """Each computation of an HLO module's text, by name, with the opcode
    of its ROOT instruction."""
    roots = {}
    for comp in re.split(r"\n(?=\S)", hlo):
        head = re.match(r"(?:ENTRY )?%(\S+) ", comp)
        root = re.search(r"ROOT %\S+ = \S+ ([\w-]+)\(", comp)
        if head and root:
            roots[head.group(1)] = root.group(1)
    return roots


@pytest.mark.parametrize("positions", ["vector", "scalar"])
def test_decode_step_writes_its_cache_in_place(positions):
    """The engine's decode program takes its cache donated and writes each
    slot's new K/V into it: the whole cache is aliased input to output,
    temp memory stays under two layers' K+V (the CPU backend gives each
    attention dot its layer's K and V as a buffer of their own, one layer
    at a time), and the only instructions outside fused computations
    with the stacked cache's shape are the cache itself and in-place
    writes (fusions whose root is a dynamic-update-slice); no copy or
    broadcast of it.  float32 throughout: with bf16 the CPU backend adds
    conversions of its own."""
    cfg = get_smoke_config("llama3.2-1b").replace(
        n_layers=4, d_model=128, n_heads=4, kv_heads=2, head_dim=64,
        param_dtype="float32", cache_dtype="float32")
    B, max_len = 16, 256
    engine = ServingEngine(cfg, init_params(KEY, cfg), max_batch=B,
                           max_len=max_len)
    pos = engine.pos if positions == "vector" else np.int32(7)
    compiled = engine._decode.lower(engine.params, engine.tokens, pos,
                                    engine.cache).compile()

    stacked = engine.cache["blocks"][0]["k"]
    layer_kv = 2 * stacked[0].nbytes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == sum(
        x.nbytes for x in jax.tree.leaves(engine.cache))
    assert mem.temp_size_in_bytes < 2 * layer_kv, mem

    hlo = compiled.as_text()
    roots = _fused_roots(hlo)
    shape = "f32[%s]" % ",".join(map(str, stacked.shape))
    found = []
    for comp in re.split(r"\n(?=\S)", hlo):
        if comp.startswith("%fused") or comp.startswith("HloModule"):
            continue
        for op, callee in re.findall(
                r"= " + re.escape(shape) + r"\{[^}]*\} ([\w-]+)\("
                r"(?:.*calls=%([\w.-]+))?", comp):
            found.append(op)
            if op == "fusion":
                assert roots[callee] == "dynamic-update-slice", callee
            else:
                assert op in ("parameter", "get-tuple-element", "bitcast"), op
    assert "fusion" in found     # the writes are there, and in place


def test_vector_pos_decode_matches_scalar():
    """Continuous-batching path: per-slot positions equal homogeneous
    decode when all slots share the position."""
    cfg = get_smoke_config("llama3.2-1b")
    params = init_params(KEY, cfg)
    B, T = 2, 8
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab)
    _, c1 = prefill(params, toks, cfg, max_len=16)
    _, c2 = prefill(params, toks, cfg, max_len=16)
    l1, _ = decode_step(params, toks[:, -1], jnp.asarray(T, jnp.int32),
                        c1, cfg)
    l2, _ = decode_step(params, toks[:, -1],
                        jnp.full((B,), T, jnp.int32), c2, cfg)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), rtol=1e-5)


def test_ce_chunking_invariant():
    """Chunked CE == unchunked CE."""
    cfg = get_smoke_config("llama3.2-1b")
    params = init_params(KEY, cfg)
    toks, _ = _batch(cfg, B=2, S=32)
    l0 = lm_loss(params, toks, toks, cfg.replace(ce_seq_chunk=0))
    l1 = lm_loss(params, toks, toks, cfg.replace(ce_seq_chunk=8))
    assert float(l0) == pytest.approx(float(l1), rel=1e-4)


def test_moe_seq_chunking_invariant():
    """MoE sequence chunking changes capacity locality, not correctness
    of the dispatch math; with generous capacity results must agree."""
    cfg = get_smoke_config("mixtral-8x22b").replace(capacity_factor=8.0)
    params = init_params(KEY, cfg)
    toks, _ = _batch(cfg, B=2, S=32)
    l0, _ = forward(params, toks, cfg.replace(moe_seq_chunk=0))
    l1, _ = forward(params, toks, cfg.replace(moe_seq_chunk=16))
    np.testing.assert_allclose(np.asarray(l0, np.float32),
                               np.asarray(l1, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_param_count_sanity():
    cfg = get_config("llama3.2-1b")
    total, active = cfg.param_count()
    assert total == active
    assert 1.1e9 < total < 1.6e9
    cfg = get_config("mixtral-8x22b")
    total, active = cfg.param_count()
    assert 1.2e11 < total < 1.6e11            # ~141B
    assert 3.0e10 < active < 4.5e10           # ~39B active

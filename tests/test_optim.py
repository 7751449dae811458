"""Optimizer, schedules, clipping, gradient compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, cosine_warmup, global_norm)
from repro.train.compression import (compress_grads, dequantize_int8,
                                     init_error_feedback, quantize_int8)


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params, cfg)

    def loss(p):
        return jnp.sum(p["x"] ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = adamw_update(g, state, params, cfg)
    assert float(loss(params)) < 1e-4


def test_adamw_bf16_state_halves_memory():
    params = {"w": jnp.zeros((64, 64), jnp.bfloat16)}
    s32 = adamw_init(params, AdamWConfig(state_dtype="float32"))
    s16 = adamw_init(params, AdamWConfig(state_dtype="bfloat16"))
    assert s32["mu"]["w"].dtype == jnp.float32
    assert s16["mu"]["w"].dtype == jnp.bfloat16


def test_cosine_warmup_shape():
    assert float(cosine_warmup(0, warmup=10, total=100)) == 0.0
    assert float(cosine_warmup(10, warmup=10, total=100)) \
        == pytest.approx(1.0)
    assert float(cosine_warmup(100, warmup=10, total=100)) \
        == pytest.approx(0.1)
    # monotone decay after warmup
    vals = [float(cosine_warmup(s, warmup=10, total=100))
            for s in range(10, 101, 10)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


# float32 values without subnormals: XLA flushes those to zero in any
# arithmetic, so "unchanged under the cap" cannot hold for them.
@given(st.lists(st.floats(-100, 100, width=32, allow_subnormal=False),
                min_size=1, max_size=32),
       st.floats(0.1, 10.0))
@settings(max_examples=100, deadline=None)
def test_clip_property(vals, max_norm):
    tree = {"g": jnp.asarray(vals, jnp.float32)}
    clipped, norm = clip_by_global_norm(tree, max_norm)
    new_norm = float(global_norm(clipped))
    assert new_norm <= max_norm * 1.01
    if float(norm) <= max_norm:     # no-op when under the cap
        np.testing.assert_allclose(np.asarray(clipped["g"]),
                                   np.asarray(tree["g"]), rtol=1e-5)


class TestCompression:
    def test_quantize_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (128,)) * 3
        q, s = quantize_int8(x)
        err = np.abs(np.asarray(dequantize_int8(q, s) - x))
        assert err.max() <= float(s) * 0.5 + 1e-6

    def test_error_feedback_accumulates_residual(self):
        g = {"w": jnp.full((16,), 0.001)}
        ef = init_error_feedback(g)
        total = jnp.zeros((16,))
        for _ in range(50):
            deq, ef = compress_grads(g, ef)
            total = total + deq["w"]
        # With EF, the long-run average equals the true gradient.
        np.testing.assert_allclose(np.asarray(total) / 50, 0.001,
                                   rtol=0.05)

    def test_train_step_with_compression_runs(self):
        from repro.configs import get_smoke_config
        from repro.models import init_params
        from repro.train.steps import StepConfig, make_train_step
        cfg = get_smoke_config("llama3.2-1b")
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = AdamWConfig(lr=1e-3)
        opt_state = adamw_init(params, opt)
        opt_state["ef"] = init_error_feedback(params)
        fn = jax.jit(make_train_step(cfg, None, opt,
                                     StepConfig(compress=True, warmup=1)))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 16), 0,
                                  cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        losses = []
        for i in range(6):
            params, opt_state, m = fn(params, opt_state,
                                      jnp.asarray(i, jnp.int32), batch)
            losses.append(float(m["loss"]))
        assert "ef" in opt_state
        assert losses[-1] < losses[0]

"""The float32 reference against the program's own forward pass, at
smoke widths on the CPU, for both served configurations."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import reference, weights  # noqa: E402
from repro.models import ModelConfig, forward, init_params  # noqa: E402

CONFIGS = ROOT / "benchmarks" / "chip" / "configs"
#: smoke widths: every structural key (biases, tied head, theta) is the
#: configuration's own
SMOKE = dict(n_layers=2, d_model=64, n_heads=8, kv_heads=2, head_dim=16,
             d_ff=160, vocab=300)


def smoke_model(name: str) -> dict:
    model = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    model.update(SMOKE)
    return model


@pytest.mark.parametrize("name", ["dsc33b-8l", "internvl2-1b"])
def test_reference_matches_forward_in_float32(name):
    model = smoke_model(name)
    model.update(dtype="float32", param_dtype="float32")
    cfg = ModelConfig(**model)
    params = weights.make_params(cfg, 5)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, 37).tolist()
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, jnp.asarray([tokens]), cfg)
    want = np.asarray(want[0, :, :cfg.vocab])
    rows = list(range(len(tokens)))
    got = reference.logits_at(params, tokens, rows, reference.Arch(model))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", ["dsc33b-8l", "internvl2-1b"])
def test_float8_control_departs_further_than_bfloat16(name):
    """The control (float8 operands) must move the logits much more than
    serving the same weights in bfloat16 does."""
    model = smoke_model(name)
    cfg = ModelConfig(**model)
    params = weights.make_params(cfg, 6)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab, 40).tolist()
    rows = list(range(len(tokens)))
    a = reference.Arch(model)
    ref = reference.logits_at(params, tokens, rows, a)
    ctl = reference.logits_at(params, tokens, rows, a, quant=True)
    bf, _ = forward(params, jnp.asarray([tokens]), cfg)
    bf = np.asarray(bf[0, :, :cfg.vocab], np.float32)
    assert np.abs(ctl - ref).max() > 3 * np.abs(bf - ref).max()


def test_padding_does_not_change_earlier_positions():
    model = smoke_model("internvl2-1b")
    cfg = ModelConfig(**model)
    params = weights.make_params(cfg, 7)
    a = reference.Arch(model)
    tokens = np.random.default_rng(2).integers(0, cfg.vocab, 600).tolist()
    long = reference.logits_at(params, tokens, [10, 499], a)
    short = reference.logits_at(params, tokens[:500], [10, 499], a)
    np.testing.assert_allclose(long, short, atol=1e-5)


def test_weights_follow_the_seed_and_the_program_layout():
    model = smoke_model("dsc33b-8l")
    cfg = ModelConfig(**model)
    a, b = weights.make_params(cfg, 2**40 + 3), weights.make_params(cfg, 3)
    c = weights.make_params(cfg, 2**40 + 3)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(x.dtype == jnp.bfloat16 for x in la)
    assert all(np.array_equal(x, z) for x, z in zip(la, lc))
    assert not np.array_equal(la[0], lb[0])
    assert jax.tree.structure(a) == jax.tree.structure(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))

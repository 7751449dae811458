"""Weights from the seed, made on the device in one jitted call.

The tree has the layout the program's ``init_params`` declares (read with
``jax.eval_shape``, so nothing is computed by the program), and every leaf
is drawn here from the seed, in the type it is served in:

* matrices: normal with standard deviation 1/√(fan-in), so activations
  keep unit scale (the embedding: 1/√d, so the tied head gives logits of
  about unit scale);
* norm scales (stored as offsets from 1): normal × 0.1;
* q/k/v biases: normal × 0.5, large enough to matter in the comparison.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, init_params

__all__ = ["make_params", "key_data"]


def key_data(seed: int) -> np.ndarray:
    """The two 32-bit words of a threefry key for a seed of up to 64 bits."""
    s = int(seed) & (2**64 - 1)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def _std(names: list[str], shape: tuple[int, ...], stacked: bool) -> float:
    name = names[-1]
    core = shape[1:] if stacked else shape
    if name == "embed":
        return 1.0 / math.sqrt(core[-1])
    if len(core) == 2:
        return 1.0 / math.sqrt(core[0])
    if name in ("bq", "bk", "bv"):
        return 0.5
    return 0.1


def make_params(cfg: ModelConfig, seed: int) -> dict:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for path, leaf in leaves:
        names = [str(p.key) for p in path
                 if isinstance(p, jax.tree_util.DictKey)]
        stacked = any(isinstance(p, jax.tree_util.DictKey)
                      and p.key == "blocks" for p in path)
        plan.append((leaf.shape, leaf.dtype, _std(names, leaf.shape,
                                                  stacked)))

    def make(kd):
        key = jax.random.wrap_key_data(kd, impl="threefry2x32")
        out = []
        for i, (shape, dtype, std) in enumerate(plan):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    params = jax.jit(make)(jnp.asarray(key_data(seed)))
    return jax.block_until_ready(params)

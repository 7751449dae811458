"""Compile the serving steps and the Pallas kernels for a TPU v5e chip at
real widths, without a chip attached.

The TPU compiler ships with jaxlib's TPU plugin and compiles for a
described topology, so these tests catch what interpret mode cannot: block
shapes the TPU lowering refuses, primitives it does not implement, and
programs that do not fit the chip's 16 GB.  Nothing runs, so they say
nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports every test module.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru import rglru_scan_kernel
from repro.kernels.rwkv6 import wkv6
from repro.models import decode_step, init_cache, init_params, prefill

V5E_HBM_BYTES = 16e9
#: the serving shapes ``chip_smoke.py`` runs
MAX_BATCH, MAX_LEN, PROMPT_BUCKET = 16, 2048, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, m
    return total


@pytest.fixture(scope="module")
def llama(one_chip):
    cfg = get_config("llama3.2-1b")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return cfg, _on(one_chip, params)


def test_decode_step_compiles_and_fits(one_chip, llama):
    cfg, params = llama
    cache = _on(one_chip, jax.eval_shape(
        lambda: init_cache(cfg, MAX_BATCH, MAX_LEN)))
    vec = jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, pos, c: decode_step(p, t, pos, c, cfg)
    ).lower(params, vec, vec, cache).compile()
    _fits(compiled)


def test_decode_step_writes_in_place_with_whole_tile_cache(one_chip):
    """Jitted as the engine jits it, the cache donated, the decode step
    compiles where the cache's minor dimensions are one whole (8, 128)
    tile (deepseek-coder-33b: 8 KV heads of 128), and its cache is
    aliased input to output.  A ``fori_loop`` over the slots' writes
    failed the TPU compiler's checks at this shape."""
    cfg = get_config("deepseek-coder-33b").replace(n_layers=2)
    params = _on(one_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = _on(one_chip, jax.eval_shape(lambda: init_cache(cfg, 16, 4096)))
    vec = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, t, pos, c: decode_step(p, t, pos, c, cfg),
        donate_argnums=(3,)).lower(params, vec, vec, cache).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    _fits(compiled)


def test_prefill_compiles_and_fits(one_chip, llama):
    cfg, params = llama
    prompt = jax.ShapeDtypeStruct((1, PROMPT_BUCKET), jnp.int32,
                                  sharding=one_chip)
    compiled = jax.jit(
        lambda p, t: prefill(p, t, cfg, max_len=MAX_LEN,
                             return_all_logits=True)
    ).lower(params, prompt).compile()
    _fits(compiled)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_flash_attention_compiles_at_llama_widths(one_chip):
    cfg = get_config("llama3.2-1b")
    S = PROMPT_BUCKET
    q = jax.ShapeDtypeStruct((1, S, cfg.n_heads, cfg.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, cfg.kv_heads, cfg.head_dim),
                              jnp.bfloat16, sharding=one_chip)
    _assert_kernel(jax.jit(flash_attention).lower(q, kv, kv).compile())


def test_wkv6_compiles_at_rwkv6_7b_widths(one_chip):
    cfg = get_config("rwkv6-7b")
    N = 64
    H = cfg.d_model // N
    seq = jax.ShapeDtypeStruct((1, H, PROMPT_BUCKET, N), jnp.float32,
                               sharding=one_chip)
    u = jax.ShapeDtypeStruct((H, N), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda r, k, v, w, u: wkv6(r, k, v, w, u, chunk=cfg.rwkv_chunk)
    ).lower(seq, seq, seq, seq, u).compile()
    _assert_kernel(compiled)


def test_rglru_compiles_at_recurrentgemma_2b_widths(one_chip):
    cfg = get_config("recurrentgemma-2b")
    ab = jax.ShapeDtypeStruct((1, PROMPT_BUCKET, cfg.rnn_width),
                              jnp.float32, sharding=one_chip)
    _assert_kernel(jax.jit(rglru_scan_kernel).lower(ab, ab).compile())

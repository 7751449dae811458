"""Chunked RWKV-6 WKV as a Pallas TPU kernel.

Grid ``(B, H, n_chunks)`` — the chunk dimension is trailing, hence
sequential on TPU, so the (N, N) fp32 state matrix lives in VMEM scratch
across chunk steps (the cross-chunk recurrence) while each chunk's
intra-block math is two masked matmuls on MXU-aligned (L, N) tiles.

The intra-chunk pairwise decay tensor (L, L, N) stays in VMEM — the
reason the chunk length is 16/32: 32·32·64 fp32 = 256 KB.  Exponent
clamping matches the jnp reference (one-sided, lossless below e⁻⁴⁰).

HBM traffic: r/k/v/w in, y out, once — the state never leaves VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6"]

_CLAMP = 40.0


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_ref, *, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    rt = r_ref[0, 0].astype(jnp.float32)          # (L, N)
    kt = k_ref[0, 0].astype(jnp.float32)
    vt = v_ref[0, 0].astype(jnp.float32)
    wt = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)              # (1, N)
    s = s_ref[...]                                # (N, N)
    L, N = rt.shape

    lw = jnp.log(jnp.clip(wt, 1e-38, None))       # ≤ 0
    # Inclusive prefix sum over the chunk as a lower-triangular matmul:
    # the TPU lowering has no cumsum.
    tri = jnp.tril(jnp.ones((L, L), jnp.float32))
    cum = jax.lax.dot_general(tri, lw, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # lc_t
    cum_ex = cum - lw                             # lc_{t-1}

    # Pairwise decay D[t, s] = exp(lc_{t-1} − lc_s), strictly causal.
    diff = cum_ex[:, None, :] - cum[None, :, :]   # (L, L, N)
    decay = jnp.exp(jnp.clip(diff, -_CLAMP, 0.0))
    scores = jnp.sum(rt[:, None, :] * kt[None, :, :] * decay, axis=-1)
    mask = jnp.tril(jnp.ones((L, L), jnp.float32), k=-1)
    scores = scores * mask
    bonus = jnp.sum(rt * u * kt, axis=-1, keepdims=True)      # (L, 1)
    y = jax.lax.dot_general(scores, vt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + bonus * vt
    r_dec = rt * jnp.exp(jnp.clip(cum_ex, -_CLAMP, 0.0))
    y = y + jax.lax.dot_general(r_dec, s, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    tail = jnp.sum(lw, axis=0, keepdims=True)     # lc_L   (1, N)
    k_tail = kt * jnp.exp(jnp.clip(tail - cum, -_CLAMP, 0.0))
    # The state decays row-wise by exp(lc_L[i]).  lw^T @ 1 gives lc_L
    # down the rows, [i, j] = lc_L[i], without a lane-to-sublane relayout.
    tail_rows = jax.lax.dot_general(
        lw, jnp.ones((L, N), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)       # (N, N)
    s_new = jnp.exp(jnp.clip(tail_rows, -_CLAMP, 0.0)) * s \
        + jax.lax.dot_general(k_tail, vt, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    @pl.when(ic == n_chunks - 1)
    def _finish():
        sout_ref[0, 0] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, s0: jax.Array | None = None, *,
         chunk: int = 32, interpret: bool = False):
    """r,k,v,w: (B, H, S, N); u: (H, N); s0: (B, H, N, N) or None.

    Returns (y (B, H, S, N) f32, s_final (B, H, N, N) f32).
    """
    B, H, S, N = r.shape
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    if s0 is None:
        s0 = jnp.zeros((B, H, N, N), jnp.float32)

    grid = (B, H, n_chunks)
    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, 1, chunk, N),
                            lambda b, h, ic: (b, h, ic, 0))
    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            # u goes in as (H, 1, N): a (1, N) block of an (H, N) array
            # breaks the TPU rule that the last two block dims tile by
            # (8, 128) or equal the array's.
            pl.BlockSpec((1, 1, N), lambda b, h, ic: (h, 0, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, N, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, N), s0)
    return y, s_fin

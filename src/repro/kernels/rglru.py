"""RG-LRU linear scan (h_t = a_t · h_{t−1} + b_t) as a Pallas TPU kernel.

Grid ``(B, n_R_blocks, n_T_blocks)`` — time blocks trail, so they run
sequentially and the per-channel hidden state persists in VMEM scratch.
Within a time block the recurrence is an in-kernel ``fori_loop`` of
vector FMAs over the (1, R_blk) lanes: this is a bandwidth-bound op (no
MXU work) and the kernel achieves the HBM-optimal traffic of reading
a/b and writing h exactly once — no log-space tricks, no numerical
clamping (contrast with the associative-scan fallback, which pays
O(log S) extra passes).

The R dimension is blocked at 512 lanes so a/b/h time-tiles fit VMEM:
3 tiles · (T_blk=256 × 512) f32 = 1.5 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_kernel"]


def _kernel(a_ref, b_ref, h0_ref, h_ref, hout_ref, state_ref, *,
            t_blk: int, n_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = h0_ref[0].astype(jnp.float32)   # (1, R_blk)

    def step(t, h):
        # Read one time row from the refs: indexing a loaded (T_blk, R_blk)
        # value at a traced t lowers to dynamic_slice, which the TPU
        # lowering does not implement; a dynamic ref window does lower.
        row = pl.ds(t, 1)
        a_t = a_ref[0, row, :].astype(jnp.float32)       # (1, R_blk)
        b_t = b_ref[0, row, :].astype(jnp.float32)
        h = a_t * h + b_t
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, t_blk, step, state_ref[...])
    state_ref[...] = h

    @pl.when(it == n_t - 1)
    def _finish():
        hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("t_blk", "r_blk", "interpret"))
def rglru_scan_kernel(a: jax.Array, b: jax.Array,
                      h0: jax.Array | None = None, *,
                      t_blk: int = 256, r_blk: int = 512,
                      interpret: bool = False):
    """a, b: (B, S, R) → h: (B, S, R) f32, h_final: (B, R) f32."""
    B, S, R = a.shape
    t_blk = min(t_blk, S)
    r_blk = min(r_blk, R)
    assert S % t_blk == 0 and R % r_blk == 0, (S, t_blk, R, r_blk)
    if h0 is None:
        h0 = jnp.zeros((B, R), jnp.float32)
    n_t = S // t_blk
    grid = (B, R // r_blk, n_t)
    kernel = functools.partial(_kernel, t_blk=t_blk, n_t=n_t)
    h, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t_blk, r_blk), lambda b_, ir, it: (b_, it, ir)),
            pl.BlockSpec((1, t_blk, r_blk), lambda b_, ir, it: (b_, it, ir)),
            pl.BlockSpec((1, 1, r_blk), lambda b_, ir, it: (b_, 0, ir)),
        ],
        out_specs=[
            pl.BlockSpec((1, t_blk, r_blk), lambda b_, ir, it: (b_, it, ir)),
            pl.BlockSpec((1, 1, r_blk), lambda b_, ir, it: (b_, 0, ir)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, R), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, R), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, r_blk), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
    return h, h_fin[:, 0]

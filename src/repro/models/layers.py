"""Core transformer layers: RMSNorm, RoPE, GQA attention (global /
sliding-window / softcap / bias), gated MLPs, and KV-cache decode paths.

Everything is pure JAX (jit/pjit-compatible); attention over long
sequences is *q-chunked* (scan over query blocks with bounded score
temporaries) so prefill_32k fits HBM without a kernel.  The Pallas flash
kernel in :mod:`repro.kernels.flash_attention` is a drop-in replacement
for the inner block math on real TPUs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "rmsnorm", "rope", "gqa_attention", "decode_gqa_attention",
    "mlp_apply", "init_attn_layer", "init_mlp",
]


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return ((1.0 + w.astype(jnp.float32)) * x).astype(dt)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (..., S, H, D); pos: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs       # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                       # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(scores: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _score_block(q_blk, k, softcap, scale):
    # q_blk: (B, Sq, K, G, D), k: (B, Skv, K, D) -> (B, K, G, Sq, Skv)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k,
                   preferred_element_type=jnp.float32) * scale
    return _softcap(s, softcap)


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int | None = None,
                  softcap: float | None = None,
                  q_chunk: int = 512,
                  pos_offset: int = 0) -> jax.Array:
    """Causal grouped-query attention over a full sequence.

    q: (B, S, H, D); k, v: (B, S, K, D) with H = K·G.  Scanned over query
    chunks: peak score temp is (B, K, G, q_chunk, kv_span) where kv_span
    is S for global layers and window + q_chunk for local ones.
    """
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, K, G, D)

    if S <= q_chunk:
        with jax.named_scope("pallas:flash_attention"):
            pos = pos_offset + jnp.arange(S)
            s = _score_block(qg, k, softcap, scale)
            mask = pos[:, None] >= pos[None, :]
            if window is not None:
                mask &= pos[:, None] - pos[None, :] < window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
        return o.reshape(B, S, H, D)

    assert S % q_chunk == 0, (S, q_chunk)
    n_blocks = S // q_chunk
    qg = qg.reshape(B, n_blocks, q_chunk, K, G, D)

    # NOTE: each chunk body is checkpointed — without this, the backward
    # pass of the scan stacks every chunk's (B,K,G,c,kv_span) probability
    # tensor as residuals, exactly the O(S²) memory the chunking avoids.
    if window is not None:
        # Local: each q block attends to a fixed-size kv span ending at the
        # block end.  Span is padded on the left so slicing is static-size.
        span = window + q_chunk
        k_pad = jnp.pad(k, ((0, 0), (span - q_chunk, 0), (0, 0), (0, 0)))
        v_pad = jnp.pad(v, ((0, 0), (span - q_chunk, 0), (0, 0), (0, 0)))

        @jax.checkpoint
        def blk(_, i):
            with jax.named_scope("pallas:flash_attention"):
                qb = qg[:, i]                               # (B,c,K,G,D)
                kb = lax.dynamic_slice_in_dim(k_pad, i * q_chunk, span,
                                              axis=1)
                vb = lax.dynamic_slice_in_dim(v_pad, i * q_chunk, span,
                                              axis=1)
                qpos = i * q_chunk + jnp.arange(q_chunk)
                kpos = i * q_chunk + jnp.arange(span) - (span - q_chunk)
                s = _score_block(qb, kb, softcap, scale)
                m = (qpos[:, None] >= kpos[None, :]) \
                    & (qpos[:, None] - kpos[None, :] < window) \
                    & (kpos[None, :] >= 0)
                s = jnp.where(m[None, None, None], s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
                return None, jnp.einsum("bkgqs,bskd->bqkgd", p, vb)

        _, o = lax.scan(blk, None, jnp.arange(n_blocks))
        o = jnp.moveaxis(o, 0, 1)                 # (B, n, c, K, G, D)
        return o.reshape(B, S, H, D)

    @jax.checkpoint
    def blk(_, i):
        with jax.named_scope("pallas:flash_attention"):
            qb = qg[:, i]
            qpos = pos_offset + i * q_chunk + jnp.arange(q_chunk)
            kpos = pos_offset + jnp.arange(S)
            s = _score_block(qb, k, softcap, scale)   # (B,K,G,c,S)
            m = qpos[:, None] >= kpos[None, :]
            s = jnp.where(m[None, None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return None, jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    _, o = lax.scan(blk, None, jnp.arange(n_blocks))
    o = jnp.moveaxis(o, 0, 1)
    return o.reshape(B, S, H, D)


def decode_gqa_attention(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, k_new: jax.Array,
                         v_new: jax.Array, pos: jax.Array, *,
                         softcap: float | None = None) -> jax.Array:
    """One-token attention against a ring cache and the token's own key.

    q: (B, 1, H, D); caches: (B, Sc, K, D), slot = position mod Sc (a
    cache longer than every position is plain linear indexing), keys
    stored post-RoPE; k_new, v_new: (B, 1, K, D), the current token's
    key and value, which the cache does not hold yet.  ``pos`` — the
    position of the current token, scalar (homogeneous batch) or (B,)
    vector (continuous batching: every slot at its own depth).  The
    cache is only read: its slot ``pos mod Sc`` still holds position
    ``pos - Sc`` and is masked, and the token's own key joins the
    softmax as one more score.
    """
    B, _, H, D = q.shape
    Sc, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(D)
    with jax.named_scope("pallas:flash_decode"):
        qg = q.reshape(B, 1, K, G, D)
        s = _score_block(qg, k_cache, softcap, scale)  # (B,K,G,1,Sc)
        s_new = _score_block(qg, k_new, softcap, scale)  # (B,K,G,1,1)
        slots = jnp.arange(Sc)
        posb = pos if getattr(pos, "ndim", 0) else jnp.full((B,), pos)
        posb = posb[:, None]                           # (B, 1)
        slot_pos = posb - ((posb - slots[None, :]) % Sc)
        valid = (slot_pos >= 0) & (slot_pos < posb)
        s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
        m = jnp.maximum(s.max(axis=-1, keepdims=True), s_new)
        p = jnp.exp(s - m)
        p_new = jnp.exp(s_new - m)
        denom = p.sum(axis=-1, keepdims=True) + p_new
        o = jnp.einsum("bkgqs,bskd->bqkgd",
                       (p / denom).astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
        o = o + jnp.einsum("bkgqs,bskd->bqkgd",
                           (p_new / denom).astype(v_new.dtype), v_new,
                           preferred_element_type=jnp.float32)
    return o.astype(v_cache.dtype).reshape(B, 1, H, D)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_apply(x: jax.Array, p: dict, kind: str) -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["w1"], approximate=True) * (x @ p["w3"])
    elif kind == "gelu":
        h = jax.nn.gelu(x @ p["w1"], approximate=True)
    else:
        raise ValueError(kind)
    return h @ p["w2"]


def init_mlp(key: jax.Array, d: int, ff: int, kind: str,
             dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    std_in = 1.0 / math.sqrt(d)
    std_out = 1.0 / math.sqrt(ff)
    p = {
        "w1": jax.random.normal(k1, (d, ff), dtype) * std_in,
        "w2": jax.random.normal(k2, (ff, d), dtype) * std_out,
    }
    if kind in ("swiglu", "geglu"):
        p["w3"] = jax.random.normal(k3, (d, ff), dtype) * std_in
    return p


def init_attn_layer(key: jax.Array, cfg, dtype) -> dict:
    """Weights for one attention block (projections + norms + MLP)."""
    d, H, K, D = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    std = 1.0 / math.sqrt(d)
    p = {
        "ln1": jnp.zeros((d,), dtype),
        "wq": jax.random.normal(ks[0], (d, H * D), dtype) * std,
        "wk": jax.random.normal(ks[1], (d, K * D), dtype) * std,
        "wv": jax.random.normal(ks[2], (d, K * D), dtype) * std,
        "wo": jax.random.normal(ks[3], (H * D, d), dtype)
        / math.sqrt(H * D),
        "ln2": jnp.zeros((d,), dtype),
        "mlp": init_mlp(ks[4], d, cfg.d_ff, cfg.mlp, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * D,), dtype)
        p["bk"] = jnp.zeros((K * D,), dtype)
        p["bv"] = jnp.zeros((K * D,), dtype)
    if cfg.post_norms:
        p["ln1_post"] = jnp.zeros((d,), dtype)
        p["ln2_post"] = jnp.zeros((d,), dtype)
    return p

"""Plain float32 reference of the served architectures.

A decoder of GQA attention with RoPE, RMSNorm and a SwiGLU MLP, with
optional q/k/v biases and a tied or untied output head (Llama and Qwen2
as published).  Written in straightforward ``jax.numpy`` from the
published equations: no cache, no batching, no kernels, and no code of
``repro``.  It reads the parameter tree the benchmark made (layout below)
and runs layer by layer under ``jax.default_matmul_precision("highest")``,
so that it fits beside the weights on one chip.

Parameter layout (the tree ``weights.make_params`` fills):

* ``embed`` (V', d), ``final_ln`` (d,), and ``lm_head`` (d, V') when the
  head is untied; V' is the vocabulary padded to a multiple of 128, and
  only the first ``vocab`` rows are tokens;
* ``blocks[0]``: one dict of arrays stacked over the layers: ``ln1``,
  ``ln2`` (d,), ``wq`` (d, H·D), ``wk``, ``wv`` (d, K·D), ``wo`` (H·D, d),
  ``mlp`` {``w1``, ``w3`` (d, F), ``w2`` (F, d)}, and ``bq``, ``bk``,
  ``bv`` with the biases;
* a norm's scale is stored as its offset from 1: the scale is ``1 + w``.

``quant=True`` runs the same equations with the operands of every
product with a weight matrix (the projections, the MLP and the head)
rounded to float8 (e4m3, one scale per weight matrix and per
activation row).  It is the precision below the served bfloat16, and
serves as the control that the comparison in ``correct`` must reject.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Arch", "logits_at"]

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: sequences are padded up to a power of two, at least this, so that one
#: compiled layer serves many lengths (attention is causal, so padding
#: after the last real token changes nothing before it)
MIN_PAD = 512
#: queries per attention block (bounds the score temporary)
Q_BLOCK = 512


class Arch:
    """The sizes the reference needs, from a configuration's ``model``."""

    def __init__(self, model: dict) -> None:
        self.layers = int(model["n_layers"])
        self.d = int(model["d_model"])
        self.heads = int(model["n_heads"])
        self.kv = int(model["kv_heads"])
        self.hd = int(model["head_dim"])
        self.ff = int(model["d_ff"])
        self.vocab = int(model["vocab"])
        self.theta = float(model["rope_theta"])
        self.eps = float(model["norm_eps"])
        self.tied = bool(model["tie_embeddings"])

    def key(self):
        return (self.layers, self.d, self.heads, self.kv, self.hd, self.ff,
                self.vocab, self.theta, self.eps, self.tied)

    def __hash__(self):
        return hash(self.key())

    def __eq__(self, other):
        return isinstance(other, Arch) and self.key() == other.key()


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis`` (None:
    one scale for the whole array), back in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, quant):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant:
        x, w = _q8(x, -1), _q8(w, None)
    return x @ w


def _norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """Rotary embedding, rotate-half form.  x: (S, heads, D)."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, a: Arch):
    """Causal GQA.  q: (S, H, D); k, v: (S, K, D).  Query head h reads
    KV head h // (H / K).  Scanned over query blocks."""
    S = q.shape[0]
    G = a.heads // a.kv
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    nb = max(1, S // Q_BLOCK)
    qb = q.reshape(nb, S // nb, a.heads, a.hd)

    def block(i):
        qi = qb[i]
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(a.hd)
        rows = i * (S // nb) + jnp.arange(S // nb)
        mask = rows[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return jax.lax.map(block, jnp.arange(nb)).reshape(S, a.heads, a.hd)


@partial(jax.jit, static_argnames=("a", "quant"))
def _layer(blocks, i, h, a: Arch, quant: bool):
    p = jax.tree.map(lambda t: t[i], blocks)
    S = h.shape[0]
    x = _norm(h, p["ln1"], a.eps)
    q = _mm(x, p["wq"], quant)
    k = _mm(x, p["wk"], quant)
    v = _mm(x, p["wv"], quant)
    if "bq" in p:
        q = q + p["bq"].astype(jnp.float32)
        k = k + p["bk"].astype(jnp.float32)
        v = v + p["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, a.heads, a.hd), a.theta)
    k = _rope(k.reshape(S, a.kv, a.hd), a.theta)
    v = v.reshape(S, a.kv, a.hd)
    o = _attention(q, k, v, a).reshape(S, a.heads * a.hd)
    h = h + _mm(o, p["wo"], quant)
    x = _norm(h, p["ln2"], a.eps)
    m = p["mlp"]
    g = _mm(x, m["w1"], quant)
    u = _mm(x, m["w3"], quant)
    return h + _mm(jax.nn.silu(g) * u, m["w2"], quant)


@partial(jax.jit, static_argnames=("a",))
def _embed(table, tokens, a: Arch):
    return table[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("a", "quant"))
def _head(params, h, rows, a: Arch, quant: bool):
    x = _norm(h[rows], params["final_ln"], a.eps)
    w = params["embed"].T if a.tied else params["lm_head"]
    return _mm(x, w, quant)[:, :a.vocab]


def logits_at(params: dict, tokens: list[int], rows: list[int], a: Arch,
              quant: bool = False) -> np.ndarray:
    """Float32 logits (len(rows), vocab) of the reference over ``tokens``,
    at the positions ``rows`` (the logits there predict the token after).
    """
    n = len(tokens)
    S = max(MIN_PAD, 1 << (n - 1).bit_length())
    ids = np.zeros((S,), np.int32)
    ids[:n] = tokens
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], jnp.asarray(ids), a)
        blocks = params["blocks"][0]
        for i in range(a.layers):
            h = _layer(blocks, jnp.int32(i), h, a, quant)
        head = {k: v for k, v in params.items()
                if k not in ("blocks", "rest")}
        # rows padded to a power of two, so few head programs compile
        m = len(rows)
        idx = np.zeros((max(16, 1 << (m - 1).bit_length()),), np.int32)
        idx[:m] = rows
        out = _head(head, h, jnp.asarray(idx), a, quant)
    return np.asarray(out)[:m]

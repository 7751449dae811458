"""Model FLOPs of the useful work in a window, from shapes.

Useful work is the prompt tokens prefilled (not their padding to a
bucket) and the tokens decoded.  A token at a position with ``c`` keys
visible (itself and those before it) costs, in each layer,
``2·d·(H·D + 2·K·D) + 2·H·D·d`` for the attention projections,
``4·H·D·c`` for the scores and the weighted sum, and ``6·d·F`` for the
SwiGLU MLP.  The output head, ``2·d·V`` over the published vocabulary,
counts once for each token served: once per prefill (only the last
position's logits serve the request) and once per decoded token.  Norms,
biases, RoPE and the softmax are left out.
"""

from __future__ import annotations

__all__ = ["dense_flops", "attn_flops", "head_flops", "prefill_flops",
           "decode_flops", "window_flops"]


def dense_flops(m: dict) -> float:
    """Projection and MLP FLOPs of one token through all layers."""
    d, H, K, D, F = (m["d_model"], m["n_heads"], m["kv_heads"],
                     m["head_dim"], m["d_ff"])
    per = 2 * d * (H * D + 2 * K * D) + 2 * H * D * d + 6 * d * F
    return float(m["n_layers"] * per)


def attn_flops(m: dict, keys: int) -> float:
    """Score and weighted-sum FLOPs, over all layers, of ``keys`` keys."""
    return float(m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * keys)


def head_flops(m: dict) -> float:
    return float(2 * m["d_model"] * m["vocab"])


def prefill_flops(m: dict, n: int) -> float:
    """An ``n``-token prompt: position i sees i + 1 keys."""
    return n * dense_flops(m) + attn_flops(m, n * (n + 1) // 2) \
        + head_flops(m)


def decode_flops(m: dict, keys: int) -> float:
    return dense_flops(m) + attn_flops(m, keys) + head_flops(m)


def window_flops(served, seconds: float, m: dict) -> float:
    """FLOPs of the tokens that requests received in the first
    ``seconds`` of the window.
    Output token j of a prompt of n tokens: j = 0 comes from the prefill,
    j >= 1 from decoding at position n + j - 1, which sees n + j keys."""
    total = 0.0
    for s in served:
        n = len(s.plan.prompt)
        for j, t in enumerate(s.stamps):
            if t >= seconds:
                break
            total += prefill_flops(m, n) if j == 0 else \
                decode_flops(m, n + j)
    return total

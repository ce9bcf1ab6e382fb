"""Forward FLOPs of a dense decoder, counted from its shapes.

What vanilla decoding with the base model would spend on a token at
context position ``t`` (0-based): two FLOPs per multiply-add of every
non-embedding weight and of the output head, plus attention's two
products (q.k and p.v) over the ``min(t + 1, window)`` keys it sees.
Embedding lookups, norms and softmax are not counted.  It does not depend
on how the program computes: drafts, rejected work and padding add
nothing.
"""

from __future__ import annotations


def matmul_params(dims) -> int:
    """Weights multiplied per token: attention, MLP and output head."""
    d, hd = dims.d_model, dims.head_dim
    attn = d * dims.n_heads * hd * 2 + d * dims.n_kv_heads * hd * 2
    mlp = (3 if dims.act == "swiglu" else 2) * d * dims.d_ff
    return dims.n_layers * (attn + mlp) + d * dims.vocab


def attn_flops_per_key(dims) -> int:
    return 4 * dims.n_layers * dims.n_heads * dims.head_dim


def _keys_sum(dims, a: int, b: int) -> int:
    """Sum over positions t in [a, b) of the keys each attends to."""
    w = dims.window or (1 << 62)
    total = 0
    lo, hi = a, min(b, w)              # t < window: t + 1 keys
    if hi > lo:
        total += (lo + 1 + hi) * (hi - lo) // 2
    lo = max(a, w)                     # t >= window: window keys
    if b > lo:
        total += w * (b - lo)
    return total


def tokens_flops(dims, a: int, b: int) -> int:
    """Forward FLOPs of the tokens at positions [a, b)."""
    if b <= a:
        return 0
    return (2 * matmul_params(dims) * (b - a)
            + attn_flops_per_key(dims) * _keys_sum(dims, a, b))

"""Plain reference of a dense decoder, in float32 ``jax.numpy``.

No kernels, cache or batching: one forward pass over a token tree, layer
by layer so that a 32-layer model fits beside its own weights.  A token
tree is a main chain (prompt and served tokens) plus branches that leave
it at some position (a draft the base rejected, the score probe after a
step): token ``i`` attends to itself and its ancestors, found through
``parent[i]``, within the sliding window.  Positions are given per token,
so a branch token sits at the position it had when it was served.

``precision="fp8"`` is the control: every matrix product takes its two
inputs rounded to float8 (e4m3), the step below the bfloat16 the
configuration states; accumulation stays float32.

The math follows the published architectures: RMSNorm or LayerNorm
(with bias), RoPE on q and k (rotate-half), grouped-query attention,
SwiGLU or tanh-GELU MLP (with biases), final norm, untied output head.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

_F8 = jnp.float8_e4m3fn
QUERY_BLOCK = 256         # divides the padded tree length (``pad_to``)


def tree_mask(parent: np.ndarray, positions: np.ndarray,
              window: int) -> np.ndarray:
    """(N, N) bool: token i attends token j, j an ancestor of i or i."""
    n = len(parent)
    mask = np.zeros((n, n), bool)
    for i in range(n):
        p = parent[i]
        if p >= 0:
            assert p < i, "a parent must come before its child"
            mask[i] = mask[p]
        mask[i, i] = True
    if window:
        mask &= positions[None, :] > positions[:, None] - window
    return mask


def _mm(spec: str, a, b, precision: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "fp8":
        # inputs rounded to float8; products and sums stay float32
        a = a.astype(_F8).astype(jnp.float32)
        b = b.astype(_F8).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, p, kind: str, eps: float):
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32))
    return (x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps)
            * p["scale"].astype(jnp.float32))


def _rope(x, positions, theta: float):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv        # (N, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]   # (N, 1, hd/2)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _layer(x, layers, idx, mask, positions, *, dims, precision):
    lp = jax.tree.map(lambda a: a[idx], layers)
    mm = functools.partial(_mm, precision=precision)
    h = _norm(x, lp["ln1"], dims.norm, dims.eps)
    a = lp["attn"]
    q = _rope(mm("nd,dhk->nhk", h, a["wq"]), positions, dims.rope_theta)
    k = _rope(mm("nd,dhk->nhk", h, a["wk"]), positions, dims.rope_theta)
    v = mm("nd,dhk->nhk", h, a["wv"])
    g = dims.n_heads // dims.n_kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)

    def attend(blk):
        # one block of queries at a time: the (heads, N, N) scores of a
        # 4k-token tree at 36 heads would not fit beside the weights
        qb, mb = blk
        s = mm("qhk,shk->hqs", qb, k) / jnp.sqrt(jnp.float32(dims.head_dim))
        s = jnp.where(mb[None], s, -jnp.inf)
        return mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v)

    n = x.shape[0]
    nb = n // QUERY_BLOCK
    o = jax.lax.map(attend, (q.reshape(nb, QUERY_BLOCK, *q.shape[1:]),
                             mask.reshape(nb, QUERY_BLOCK, n)))
    x = x + mm("qhk,hkd->qd", o.reshape(q.shape), a["wo"])
    h = _norm(x, lp["ln2"], dims.norm, dims.eps)
    m = lp["mlp"]
    if dims.act == "swiglu":
        u = jax.nn.silu(mm("nd,df->nf", h, m["w_gate"])) \
            * mm("nd,df->nf", h, m["w_up"])
        return x + mm("nf,fd->nd", u, m["w_down"])
    u = jax.nn.gelu(mm("nd,df->nf", h, m["w_in"])
                    + m["b_in"].astype(jnp.float32), approximate=True)
    return x + mm("nf,fd->nd", u, m["w_out"]) + m["b_out"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _head(x, final_norm, unembed, *, dims, precision):
    h = _norm(x, final_norm, dims.norm, dims.eps)
    return _mm("nd,dv->nv", h, unembed, precision)


@jax.jit
def _embed(tok_embed, tokens):
    return jnp.take(tok_embed, tokens, axis=0).astype(jnp.float32)


def logits(params: Dict, dims, tokens: np.ndarray, parent: np.ndarray,
           positions: np.ndarray,
           rows: np.ndarray, precision: str = "f32",
           n_layers: Optional[int] = None,
           pad_to: int = QUERY_BLOCK) -> np.ndarray:
    """(len(rows), V) float32 logits at the tree tokens ``rows``.
    ``n_layers`` runs only the first layers (the early-exit drafter).  The
    tree and ``rows`` are padded to a multiple of ``pad_to`` (pad tokens
    attend only to themselves) so that few shapes compile."""
    n = len(tokens)
    size = -(-n // pad_to) * pad_to
    tok = np.zeros(size, np.int32)
    tok[:n] = tokens
    pos = np.zeros(size, np.int32)
    pos[:n] = positions
    par = np.full(size, -1, np.int64)
    par[:n] = parent
    mask = jnp.asarray(tree_mask(par, pos, dims.window))
    pos = jnp.asarray(pos)
    x = _embed(params["tok_embed"], jnp.asarray(tok))
    for i in range(n_layers or dims.n_layers):
        x = _layer(x, params["layers"], i, mask, pos, dims=dims,
                   precision=precision)
    take = np.zeros(-(-len(rows) // pad_to) * pad_to, np.int32)
    take[:len(rows)] = rows
    x = jnp.take(x, jnp.asarray(take), axis=0)
    out = _head(x, params["final_norm"], params["unembed"], dims=dims,
                precision=precision)
    return np.asarray(out[:len(rows)])

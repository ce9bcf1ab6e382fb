"""Attention: GQA self-attention (full/causal/sliding), cross-attention,
single-token decode against a (possibly ring-buffered) KV cache.

This module is the XLA-native reference path used for training, the
multi-pod dry-run and CPU execution.  The Pallas kernels in
``repro.kernels`` implement the same math with explicit VMEM tiling for the
TPU target; ``repro.kernels.ops`` can be swapped in via ``use_pallas``
switches in the model.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from .config import ModelConfig
from .layers import ParamSpec, apply_rope
from .sharding import constrain

NEG_INF = -1e30


def attn_spec(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, k = cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"),
                        "scaled", 1.0, 0),
        "wk": ParamSpec((d, k, hd), ("embed", "kv_heads", "head_dim"),
                        "scaled", 1.0, 0),
        "wv": ParamSpec((d, k, hd), ("embed", "kv_heads", "head_dim"),
                        "scaled", 1.0, 0),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"),
                        "scaled", 1.0, 2),
    }


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, K, hd) -> (B, S, K*n_rep, hd)."""
    if n_rep == 1:
        return x
    b, s, k, hd = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, k, n_rep, hd))
    return x.reshape(b, s, k * n_rep, hd)


def qkv(x: jax.Array, p: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    return q, k, v


def out_proj(o: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    # "act_out_heads" is the heads dim at the contraction boundary: the
    # default rules keep it on the model axis (partial-sum dot + psum, the
    # cheap baseline), the exact-TP serving rules map it to None — forcing
    # the all-gather BEFORE the contraction so the dot runs replicated with
    # the same reduction order as a single device (bitwise-identical
    # logits; see DESIGN.md §Sharded serving).
    o = constrain(o, ("act_batch", None, "act_out_heads", None))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
         mask: Optional[jax.Array]) -> jax.Array:
    """q: (B,Sq,H,hd); k,v: (B,Sk,H,hd); mask broadcastable (B,H,Sq,Sk).

    Scores accumulate in f32 via ``preferred_element_type`` — NOT via an
    explicit cast of q/k, which would materialize an f32 copy of the whole
    KV cache per decode layer (2x cache HBM traffic; found and fixed in
    EXPERIMENTS.md §Perf iteration q1)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bshk->bqhk", probs.astype(v.dtype), v)


def blockwise_sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_start, causal: bool = True, window: int = 0,
                   kv_valid_upto=None, block_q: int = 512,
                   block_k: int = 1024) -> jax.Array:
    """Flash-style online-softmax attention expressed in XLA (scan over
    query blocks, scan over kv blocks) — O(S·block) memory instead of the
    O(S^2) score matrix.  This is the memory-feasible path the dry-run
    compiles for train_4k/prefill_32k; the Pallas kernel in
    repro.kernels.flash_attention is its TPU-native twin.

    q: (B,Sq,H,hd); k,v: (B,Sk,K,hd) with H % K == 0 — GQA is handled by
    GROUPING query heads per kv head (no materialized kv repetition: the
    memory/collective win is quantified in EXPERIMENTS.md §Perf; K == H is
    plain MHA and costs nothing extra).
    q absolute positions = q_start + arange(Sq); key positions = arange(Sk).
    valid(j,i): j <= pos_i (causal), j > pos_i - window (if window),
    j < kv_valid_upto (if given)."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    assert h % kh == 0
    g = h // kh
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (sq + pad_q) // bq, (sk + pad_k) // bk
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    qb = q.reshape(b, nq, bq, kh, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(b, nk, bk, kh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, bk, kh, hd).transpose(1, 0, 2, 3, 4)
    # Shard the attention math by kv-head groups over the model axis
    # (uneven/padded sharding is fine for intermediates).  Without this,
    # head_dim-sharded projections force a partial-sum ALL-REDUCE OF THE
    # SCORE MATRIX per block pair — the dominant collective term in the
    # baseline yi-34b/starcoder2 prefill roofline (EXPERIMENTS.md §Perf).
    qb = constrain(qb, (None, "act_batch", None, "act_kv", None, None))
    kb = constrain(kb, (None, "act_batch", None, "act_kv", None))
    vb = constrain(vb, (None, "act_batch", None, "act_kv", None))

    def q_block(carry, iq_and_q):
        iq, qi = iq_and_q                       # qi: (b, bq, kh, g, hd)
        qpos = q_start + iq * bq + jnp.arange(bq)

        def kv_block(acc, ik_and_kv):
            ik, kk, vv = ik_and_kv              # kk/vv: (b, bk, kh, hd)
            m_prev, l_prev, o_prev = acc
            kpos = ik * bk + jnp.arange(bk)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qi.astype(jnp.float32),
                           kk.astype(jnp.float32)) * scale
            valid = jnp.ones((bq, bk), bool)
            if causal:
                valid = valid & (kpos[None, :] <= qpos[:, None])
                if window:
                    valid = valid & (kpos[None, :] > qpos[:, None] - window)
            if kv_valid_upto is not None:
                valid = valid & (kpos[None, :] < kv_valid_upto)
            valid = valid & (kpos[None, :] < sk)   # kv padding
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            o_new = (o_prev * alpha[..., None]
                     + jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vv.dtype),
                                  vv).astype(jnp.float32))
            return (m_new, l_new, o_new), None

        init = (jnp.full((b, kh, g, bq), NEG_INF, jnp.float32),
                jnp.zeros((b, kh, g, bq), jnp.float32),
                jnp.zeros((b, kh, g, bq, hd), jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_block, init,
                                    (jnp.arange(nk), kb, vb))
        l = jnp.where(l == 0.0, 1.0, l)
        out = (o / l[..., None]).transpose(0, 3, 1, 2, 4)  # (b,bq,kh,g,hd)
        return carry, out.reshape(b, bq, h, hd).astype(q.dtype)

    _, blocks = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))
    out = blocks.transpose(1, 0, 2, 3, 4).reshape(b, nq * bq, h, hd)
    return out[:, :sq]


# use blockwise attention once the score matrix would exceed this
_BLOCKWISE_THRESHOLD = 512 * 2048


def causal_mask(sq: int, sk: int, window: int = 0,
                q_offset: int = 0) -> jax.Array:
    """(1, 1, sq, sk) boolean: query i attends key j iff j <= i (+window)."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window:
        m = m & (kj > qi - window)
    return m[None, None]


def self_attention(x: jax.Array, p: Dict[str, jax.Array], cfg: ModelConfig,
                   positions: jax.Array, window: int = 0) -> jax.Array:
    """Full-sequence causal self-attention (training / prefill)."""
    q, k, v = qkv(x, p)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    s = x.shape[1]
    if s * s > _BLOCKWISE_THRESHOLD:
        # grouped-GQA blockwise path: no kv head repetition in HBM
        o = blockwise_sdpa(q, k, v, jnp.zeros((), jnp.int32), causal=True,
                           window=window)
    else:
        n_rep = cfg.n_heads // cfg.n_kv_heads
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
        mask = causal_mask(s, s, window=window)
        o = sdpa(q, k, v, mask)
    return out_proj(o, p)


def cross_attention(x: jax.Array, kv_src: Optional[jax.Array],
                    p: Dict[str, jax.Array], cfg: ModelConfig,
                    cached_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                    ) -> jax.Array:
    """Cross-attention to encoder/image states. kv may be precomputed
    (decode path caches it once at prefill)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cached_kv is not None:
        k, v = cached_kv
    else:
        k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])
    if q.shape[1] * k.shape[1] > _BLOCKWISE_THRESHOLD:
        o = blockwise_sdpa(q, k, v, jnp.zeros((), jnp.int32), causal=False)
    else:
        n_rep = cfg.n_heads // cfg.n_kv_heads
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
        o = sdpa(q, k, v, None)
    return out_proj(o, p)


def cross_kv(kv_src: jax.Array, p: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])
    return k, v


# ---------------------------------------------------------------------------
# Cached paths: one token (decode) or a chunk (prefill) against the KV cache
# ---------------------------------------------------------------------------
#
# Both take the WHOLE stacked cache (L, B, C, K*hd) (kvcache.DecodeState)
# and the layer to work on.  The model's layer scan carries that cache, so
# each layer writes only its new tokens' slots in place and reads its first
# ``width`` slots (the attended window) with one ``dynamic_slice``: no
# per-layer restack of the cache, and no copy of it when the caller
# donates it.


def _stored_layout(cache: jax.Array) -> jax.Array:
    """Pin the carried cache to the row-major layout it is stored in.
    Left free, the compiler lays the scan's carry out as the decode
    read's dot likes it (slot-minor) and copies the whole cache into and
    out of that layout on every call."""
    return with_layout_constraint(
        cache, Layout(major_to_minor=tuple(range(cache.ndim))))


def _window(cache: jax.Array, layer, width: int, kv_heads: int
            ) -> jax.Array:
    """Slots ``[0, width)`` of layer ``layer``: (B, width, K, hd)."""
    _, b, _, f = cache.shape
    w = jax.lax.dynamic_slice(cache, (layer, 0, 0, 0), (1, b, width, f))[0]
    return w.reshape(b, width, kv_heads, f // kv_heads)


def decode_self_attention(x: jax.Array, p: Dict[str, jax.Array],
                          cfg: ModelConfig, k_cache: jax.Array,
                          v_cache: jax.Array, layer, pos: jax.Array,
                          width: int, ring: bool = False,
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode.

    x: (B, 1, d); k_cache/v_cache: the stacked (L, B, C, K*hd) caches,
    C = max_len (linear) or window (ring buffer); ``layer`` picks this
    layer's.  pos: int32 — number of tokens already in context (the new
    token's absolute position).  Either a scalar (all rows aligned — the
    single-request engine) or a (B,) vector (ragged rows — the
    continuous-batching engine): with a vector, each row writes at its own
    slot and masks by its own length.  Attends slots ``[0, width)``
    (static; ``width == C`` for a ring buffer): the caller keeps every
    written position below it.

    Returns (attn_out (B,1,d), new_k_cache, new_v_cache).
    """
    b, _, _ = x.shape
    cap = k_cache.shape[2]
    per_row = jnp.ndim(pos) == 1
    with jax.named_scope("attn"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if cfg.use_rope:
            if per_row:
                posv = pos.astype(jnp.int32)[:, None]
            else:
                posv = jnp.full((b, 1), pos, dtype=jnp.int32)
            q = apply_rope(q, posv, cfg.rope_theta)
            k = apply_rope(k, posv, cfg.rope_theta)

    slot = ((pos % cap) if ring else jnp.minimum(pos, cap - 1)
            ).astype(jnp.int32)
    with jax.named_scope("kv_write"):
        k_cache = _stored_layout(_write_slot(k_cache, k, layer, slot))
        v_cache = _stored_layout(_write_slot(v_cache, v, layer, slot))

    with jax.named_scope("attn"):
        # GQA-grouped flash-decode (the XLA twin of
        # kernels/decode_attention): no kv-head repetition, no f32 cache
        # copies, and the attention math is sharded by kv-head groups over
        # the model axis — without the constraint, a head_dim-sharded cache
        # costs one f32 cache ALL-GATHER per layer per token
        # (EXPERIMENTS.md §Perf iteration q2).
        kh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        hd = q.shape[-1]
        qg = q.reshape(b, kh, g, hd)
        qg = constrain(qg, ("act_batch", "act_kv", None, None))
        kc = constrain(_window(k_cache, layer, width, kh),
                       ("act_batch", "act_cache_seq", "act_kv", None))
        vc = constrain(_window(v_cache, layer, width, kh),
                       ("act_batch", "act_cache_seq", "act_kv", None))
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        # valid entries: linear -> j <= pos (within the sliding window if
        # any); ring -> every slot written so far (the buffer IS the
        # window)
        j = jnp.arange(width).reshape(1, 1, 1, width)
        pos_b = pos[:, None, None, None] if per_row else pos
        if ring:
            mask = (j < jnp.minimum(pos_b + 1, cap))
        else:
            mask = (j <= pos_b)
            if cfg.sliding_window:
                mask = mask & (j > pos_b - cfg.sliding_window)
        scores = jnp.where(mask, scores, NEG_INF)       # (b, kh, g, width)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(vc.dtype), vc)
        out = out.reshape(b, 1, cfg.n_heads, hd)
        out = out_proj(out, p)
    return out, k_cache, v_cache


def _write_slot(cache: jax.Array, new: jax.Array, layer,
                slot: jax.Array) -> jax.Array:
    """Write new (B, 1, K, hd) into layer ``layer`` at ``slot``: a scalar
    slot for every row in one ``dynamic_update_slice``, a (B,) vector of
    per-row slots in B one-slot ones.  Either touches only the new
    slots: neither a select over the cache nor a scatter, which XLA:CPU
    lowers to a scalar loop."""
    b = new.shape[0]
    new = new.astype(cache.dtype).reshape(1, b, 1, -1)   # (1, B, 1, K*hd)
    if jnp.ndim(slot) == 0:
        return jax.lax.dynamic_update_slice(cache, new, (layer, 0, slot, 0))
    for r in range(b):
        cache = jax.lax.dynamic_update_slice(
            cache, new[:, r:r + 1], (layer, r, slot[r], 0))
    return cache


def prefill_self_attention(x: jax.Array, p: Dict[str, jax.Array],
                           cfg: ModelConfig, k_cache: jax.Array,
                           v_cache: jax.Array, layer, start: jax.Array,
                           width: int, window: int = 0,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked prefill: process S new tokens starting at absolute position
    ``start``, writing them into layer ``layer`` of the stacked linear
    caches (L, B, C, K*hd) and attending over slots ``[0, width)``
    (static; the caller keeps every real token below it).  Used both for
    prompt prefill and SpecReason's verification/extension passes.

    ``start`` is a scalar (all rows aligned) or a (B,) vector (ragged
    rows — the continuous-batching engine's length-bucketed extends): with
    a vector, each row's chunk is scattered at its own offset and masked
    by its own positions."""
    b, s, _ = x.shape
    cap = k_cache.shape[2]
    per_row = jnp.ndim(start) == 1
    with jax.named_scope("attn"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if per_row:
            posv = (start[:, None]
                    + jnp.arange(s)[None, :]).astype(jnp.int32)
        else:
            posv = jnp.broadcast_to(
                (start + jnp.arange(s))[None, :].astype(jnp.int32), (b, s))
        if cfg.use_rope:
            q = apply_rope(q, posv, cfg.rope_theta)
            k = apply_rope(k, posv, cfg.rope_theta)
    with jax.named_scope("kv_write"):
        kn = k.astype(k_cache.dtype).reshape(b, s, -1)   # (B, S, K*hd)
        vn = v.astype(v_cache.dtype).reshape(b, s, -1)
        if per_row:
            # per-row scatter; trailing-pad writes past capacity are
            # clamped into the last slot, which is harmless for the same
            # reason trailing pads are (overwritten before it becomes
            # visible) as long as the caller keeps real contexts below
            # capacity (asserted by the batch engine).
            idx = jnp.minimum(posv, cap - 1)
            rows = jnp.arange(b)[:, None]
            k_cache = k_cache.at[layer, rows, idx].set(kn)
            v_cache = v_cache.at[layer, rows, idx].set(vn)
        else:
            at = (layer, 0, start.astype(jnp.int32), 0)
            k_cache = jax.lax.dynamic_update_slice(k_cache, kn[None], at)
            v_cache = jax.lax.dynamic_update_slice(v_cache, vn[None], at)
    with jax.named_scope("attn"):
        kw = _window(k_cache, layer, width, cfg.n_kv_heads)
        vw = _window(v_cache, layer, width, cfg.n_kv_heads)
        if not per_row and s * width > _BLOCKWISE_THRESHOLD:
            # grouped-GQA blockwise path: no kv head repetition in HBM
            out = blockwise_sdpa(q, kw, vw, start, causal=True,
                                 window=window)
        else:
            n_rep = cfg.n_heads // cfg.n_kv_heads
            kf = _repeat_kv(kw, n_rep)
            vf = _repeat_kv(vw, n_rep)
            kj = jnp.arange(width)
            if per_row:
                # (b, s, width)
                mask = (kj[None, None, :] <= posv[:, :, None])
                if window:
                    mask = mask & (kj[None, None, :]
                                   > posv[:, :, None] - window)
                out = sdpa(q, kf, vf, mask[:, None])
            else:
                qi = (start + jnp.arange(s))[:, None]
                mask = (kj[None, :] <= qi)
                if window:
                    mask = mask & (kj[None, :] > qi - window)
                out = sdpa(q, kf, vf, mask[None, None])
        out = out_proj(out, p)
    return out, k_cache, v_cache

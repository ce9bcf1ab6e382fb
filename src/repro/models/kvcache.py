"""Decode-state containers + the SpecReason rollback abstraction.

A :class:`DecodeState` bundles everything a model needs to continue
generation: attention KV caches (linear or ring-buffered sliding window),
Mamba conv/SSM states, precomputed cross-attention KV (VLM image tokens /
whisper encoder states), and the current absolute position.

Because JAX states are immutable pytrees, SpecReason's *rollback on
rejected speculative steps* is free: the controller snapshots a state by
keeping the reference and restores by using it again.  For attention caches
a rollback is also expressible as ``truncate`` (reset ``pos``; stale
entries are masked out by position), which is what the paper's "discard the
KV entries" maps to.  For SSM/hybrid states truncation is impossible —
snapshot/restore is the only correct mechanism, as noted in DESIGN.md.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    # self-attention KV caches, stacked over layers: (L, B, C, K*hd).
    # Heads and head_dim share one minor dim so the stored layout is the
    # plain row-major one on TPU as well: with (K, hd) = (32, 96) minor,
    # hd would pad to 128 and the compiler stores the cache slot-minor, a
    # layout that the in-place writes and the attention read copy the
    # whole cache out of and back into on every call.
    k: Optional[jax.Array]
    v: Optional[jax.Array]
    # mamba states: conv (L, B, W-1, ch), ssm (L, B, H, P, N)
    conv: Optional[jax.Array]
    ssm: Optional[jax.Array]
    # cross-attention KV, stacked over cross layers: (Lc, B, S_src, K, hd)
    cross_k: Optional[jax.Array]
    cross_v: Optional[jax.Array]
    # absolute position = number of tokens already in context
    pos: jax.Array
    # static: ring-buffer semantics for the attention cache?
    ring: bool = dataclasses.field(metadata=dict(static=True), default=False)

    @property
    def capacity(self) -> int:
        return self.k.shape[2] if self.k is not None else 0

    def truncate(self, new_pos) -> "DecodeState":
        """Roll the *attention* portion back to an earlier position.

        Only valid when the model is attention-only (k/v caches mask by
        position).  States with SSM components must roll back via snapshot
        references instead."""
        if self.ssm is not None:
            raise ValueError(
                "truncate() cannot roll back SSM state; keep a snapshot of "
                "the DecodeState at the step boundary and restore it.")
        return dataclasses.replace(self, pos=jnp.asarray(new_pos, jnp.int32))

    def snapshot(self) -> "DecodeState":
        """Immutable pytree — a snapshot is the object itself."""
        return self


def make_decode_state(cfg, batch: int, capacity: int, dtype=jnp.float32,
                      ring: bool = False,
                      n_cross_src: int = 0) -> DecodeState:
    """Allocate a zeroed decode state for ``cfg``.

    capacity: attention cache length (sequence capacity or window size).
    n_cross_src: number of cross-attended source tokens (image patches /
    encoder frames); 0 to omit cross caches.
    """
    hd = cfg.resolved_head_dim
    kv = cfg.n_kv_heads
    k = v = conv = ssm = ck = cv = None

    if cfg.has_attention:
        n_attn = cfg.n_self_layers if cfg.family == "vlm" else cfg.n_layers
        k = jnp.zeros((n_attn, batch, capacity, kv * hd), dtype)
        v = jnp.zeros_like(k)
    if cfg.has_ssm:
        ch = cfg.ssm_d_inner + 2 * cfg.ssm_n_groups * cfg.ssm_state
        conv = jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv_width - 1, ch),
                         dtype)
        ssm = jnp.zeros((cfg.n_layers, batch, cfg.ssm_n_heads,
                         cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    n_cross = cfg.n_cross_layers
    if n_cross and n_cross_src:
        ck = jnp.zeros((n_cross, batch, n_cross_src, kv, hd), dtype)
        cv = jnp.zeros_like(ck)

    return DecodeState(k=k, v=v, conv=conv, ssm=ssm, cross_k=ck, cross_v=cv,
                       pos=jnp.zeros((), jnp.int32), ring=ring)


def state_bytes(state: DecodeState) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(state) if hasattr(x, "size"))

"""Allocation-free input specifications for every (arch x input-shape)
combination — ShapeDtypeStruct stand-ins (weak-type-correct, shardable)
consumed by the multi-pod dry-run.

For each shape kind this module also builds the step function to lower:
  train_4k     -> train_step(params, opt_state, batch)
  prefill_32k  -> prefill(params, tokens, state)
  decode_32k   -> serve_step(params, state, tokens)   [one token, full cache]
  long_500k    -> serve_step with a ring-buffer sliding-window cache for
                  attention families (sub-quadratic per DESIGN.md), native
                  constant-state decode for SSM/hybrid.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.config import InputShape, ModelConfig, INPUT_SHAPES
from ..models.kvcache import DecodeState
from ..models.model import Model
from ..training.loss import make_train_step
from ..training.optimizer import AdamWConfig, abstract_state
from . import mesh as meshlib

Pytree = Any


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_decode_state(cfg: ModelConfig, batch: int, capacity: int,
                          dtype=jnp.bfloat16, ring: bool = False,
                          n_cross_src: int = 0) -> DecodeState:
    """ShapeDtypeStruct mirror of make_decode_state (no allocation)."""
    hd = cfg.resolved_head_dim
    kv = cfg.n_kv_heads
    k = v = conv = ssm = ck = cv = None
    if cfg.has_attention:
        n_attn = cfg.n_self_layers if cfg.family == "vlm" else cfg.n_layers
        k = _sds((n_attn, batch, capacity, kv * hd), dtype)
        v = _sds((n_attn, batch, capacity, kv * hd), dtype)
    if cfg.has_ssm:
        ch = cfg.ssm_d_inner + 2 * cfg.ssm_n_groups * cfg.ssm_state
        conv = _sds((cfg.n_layers, batch, cfg.ssm_conv_width - 1, ch), dtype)
        ssm = _sds((cfg.n_layers, batch, cfg.ssm_n_heads, cfg.ssm_head_dim,
                    cfg.ssm_state), jnp.float32)
    if cfg.n_cross_layers and n_cross_src:
        ck = _sds((cfg.n_cross_layers, batch, n_cross_src, kv, hd), dtype)
        cv = _sds((cfg.n_cross_layers, batch, n_cross_src, kv, hd), dtype)
    return DecodeState(k=k, v=v, conv=conv, ssm=ssm, cross_k=ck, cross_v=cv,
                       pos=_sds((), jnp.int32), ring=ring)


def decode_capacity(cfg: ModelConfig, shape: InputShape
                    ) -> Tuple[int, bool]:
    """(attention cache capacity, ring?) for a decode shape."""
    if not cfg.has_attention:
        return 0, False
    if shape.seq_len > 65536:
        # long-context decode: sliding-window ring buffer
        window = cfg.sliding_window or cfg.long_context_window
        return min(window, shape.seq_len), True
    if cfg.sliding_window and cfg.sliding_window < shape.seq_len:
        # SWA archs never need more physical cache than their window
        return cfg.sliding_window, True
    return shape.seq_len, False


def cross_src_len(cfg: ModelConfig) -> int:
    if cfg.family == "vlm":
        return cfg.n_image_tokens
    if cfg.family == "encdec":
        return cfg.encoder_seq_len
    return 0


@dataclasses.dataclass
class LoweringSpec:
    """Everything needed to lower one (arch x shape): fn + abstract args +
    shardings aligned with the args pytree."""
    name: str
    fn: Callable
    args: Tuple
    in_shardings: Tuple
    donate: Tuple[int, ...] = ()


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def _state_pspec(cfg: ModelConfig, state: DecodeState, batch_axes,
                 mesh, shard_seq: Optional[str] = None,
                 decode: bool = False) -> DecodeState:
    """PartitionSpec tree matching a DecodeState (shape/divisibility
    aware).  For the self-attention cache: prefer kv heads on "model";
    when they don't divide, DECODE shards the sequence dim instead
    (sequence-parallel flash-decode — §Perf iteration q2: an hd-sharded
    cache costs an f32 cache all-gather per layer per token), while
    PREFILL keeps it unsplit."""
    b = batch_axes
    msize = mesh.shape["model"]

    def kv_spec(x):
        if x is None:
            return None
        # cross caches (L, B, C, K, hd)
        if x.shape[3] % msize == 0:
            return P(None, b, shard_seq, "model", None)
        if x.shape[4] % msize == 0:
            return P(None, b, shard_seq, None, "model")
        return P(None, b, shard_seq, None, None)

    def self_kv_spec(x):
        if x is None:
            return None
        # (L, B, C, K*hd): whole kv heads on "model" when they divide; a
        # head_dim split is no slice of the heads-major minor dim, so
        # prefill then keeps the cache unsplit
        if cfg.n_kv_heads % msize == 0:
            return P(None, b, shard_seq, "model")
        if decode and x.shape[2] % msize == 0:
            return P(None, b, "model", None)
        return P(None, b, shard_seq, None)

    def ssm_spec(x):
        if x is None:
            return None
        # (L, B, H, P, N): prefer H on model, fall back to P
        if x.shape[2] % msize == 0:
            return P(None, b, "model", None, None)
        if x.shape[3] % msize == 0:
            return P(None, b, None, "model", None)
        return P(None, b, None, None, None)

    def conv_spec(x):
        if x is None:
            return None
        return (P(None, b, None, "model") if x.shape[3] % msize == 0
                else P(None, b, None, None))

    return DecodeState(
        k=self_kv_spec(state.k), v=self_kv_spec(state.v),
        conv=conv_spec(state.conv), ssm=ssm_spec(state.ssm),
        cross_k=kv_spec(state.cross_k), cross_v=kv_spec(state.cross_v),
        pos=P(), ring=state.ring)


def build_lowering(cfg: ModelConfig, shape: InputShape, mesh,
                   param_mode: str = "tp",
                   shard_cache_seq: bool = False,
                   n_microbatches: int = 1,
                   dtype=jnp.bfloat16) -> LoweringSpec:
    """Construct the LoweringSpec for one (arch, shape, mesh) combination.

    shard_cache_seq: beyond-paper option — shard the decode KV cache's
    sequence dim over the data axis (sequence-parallel attention) when the
    batch cannot use it (long_500k batch=1)."""
    model = Model(cfg)
    rules = meshlib.param_rules(param_mode)
    mesh_shape = dict(mesh.shape)
    pspecs = model.partition_specs(rules, mesh_shape=mesh_shape)
    params_abs = model.abstract(dtype)
    params_sh = _named(mesh, pspecs)
    baxes = meshlib.batch_axes(mesh, shape.global_batch)
    bspec = baxes  # None or tuple of axis names

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        step = make_train_step(model, opt_cfg, n_microbatches)
        opt_abs = abstract_state(params_abs)
        opt_sh = type(opt_abs)(
            step=NamedSharding(mesh, P()),
            m=_named(mesh, pspecs), v=_named(mesh, pspecs))
        batch = {
            "tokens": _sds((shape.global_batch, shape.seq_len), jnp.int32),
            "targets": _sds((shape.global_batch, shape.seq_len), jnp.int32),
            "weights": _sds((shape.global_batch, shape.seq_len), jnp.float32),
        }
        bsh = {k: NamedSharding(mesh, P(bspec, None)) for k in batch}
        if cfg.family == "vlm":
            batch["image_embeds"] = _sds(
                (shape.global_batch, cfg.n_image_tokens, cfg.d_model), dtype)
            bsh["image_embeds"] = NamedSharding(mesh, P(bspec, None, None))
        if cfg.family == "encdec":
            batch["encoder_embeds"] = _sds(
                (shape.global_batch, cfg.encoder_seq_len, cfg.d_model), dtype)
            bsh["encoder_embeds"] = NamedSharding(mesh, P(bspec, None, None))
        return LoweringSpec(
            name=f"{cfg.name}:{shape.name}:train_step",
            fn=step, args=(params_abs, opt_abs, batch),
            in_shardings=(params_sh, opt_sh, bsh), donate=(0, 1))

    if shape.kind == "prefill":
        ncs = cross_src_len(cfg)
        state = abstract_decode_state(cfg, shape.global_batch, shape.seq_len,
                                      dtype, ring=False, n_cross_src=ncs)
        st_sh = _named(mesh, _state_pspec(cfg, state, bspec, mesh))
        toks = _sds((shape.global_batch, shape.seq_len), jnp.int32)
        return LoweringSpec(
            name=f"{cfg.name}:{shape.name}:prefill",
            fn=model.prefill, args=(params_abs, toks, state),
            in_shardings=(params_sh, NamedSharding(mesh, P(bspec, None)),
                          st_sh), donate=(2,))

    # decode
    cap, ring = decode_capacity(cfg, shape)
    ncs = cross_src_len(cfg)
    state = abstract_decode_state(cfg, shape.global_batch, max(cap, 1) if
                                  cfg.has_attention else 0, dtype,
                                  ring=ring, n_cross_src=ncs)
    seq_axis = "data" if (shard_cache_seq and bspec is None) else None
    st_sh = _named(mesh, _state_pspec(cfg, state, bspec, mesh,
                                      shard_seq=seq_axis, decode=True))
    toks = _sds((shape.global_batch, 1), jnp.int32)
    return LoweringSpec(
        name=f"{cfg.name}:{shape.name}:serve_step",
        fn=model.decode_step, args=(params_abs, state, toks),
        in_shardings=(params_sh, st_sh, NamedSharding(mesh, P(bspec, None))),
        donate=(1,))

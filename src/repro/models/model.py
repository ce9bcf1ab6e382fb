"""Composable model assembly for all six architecture families.

Every family is built from the same substrate (layers/attention/moe/mamba2)
with parameters stacked over the layer dimension and executed with
``jax.lax.scan`` — essential to keep HLO size and compile time bounded for
the 94-layer qwen3-moe dry-run.

Public surface (all pure functions over params pytrees):
  Model.forward      — full-sequence training/eval forward -> (logits, aux)
  Model.prefill      — chunked prefill/extend from state.pos -> (logits, state)
  Model.decode_step  — one-token decode -> (logits, state)
  Model.encode       — whisper encoder (stub audio-frame embeddings in)
  Model.init / abstract / partition_specs — parameter lifecycle
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba2, moe
from .config import ModelConfig
from .kvcache import DecodeState, make_decode_state
from .layers import (ParamSpec, abstract_params, apply_mlp, apply_norm,
                     embed_spec, init_params, is_spec, mlp_spec, norm_spec,
                     partition_specs, sinusoidal_positions, unembed_spec)
from .sharding import constrain

Pytree = Any


def _stack_spec(tree: Pytree, n: int) -> Pytree:
    def one(s: ParamSpec) -> ParamSpec:
        fan = s.fan_in_axis
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale,
                         None if fan is None else fan + 1)
    return jax.tree.map(one, tree, is_leaf=is_spec)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg.validate()

    # ------------------------------------------------------------- params --
    def _layer_spec(self) -> Dict[str, Pytree]:
        cfg = self.cfg
        d = cfg.d_model
        nt = cfg.norm_type
        base = {"ln1": norm_spec(d, nt)}
        if cfg.family == "ssm":
            base["mixer"] = mamba2.mamba_spec(cfg)
            return base
        base["attn"] = attn.attn_spec(cfg)
        if cfg.family == "hybrid":
            base["mamba"] = mamba2.mamba_spec(cfg)
        if cfg.family == "moe":
            base["ln2"] = norm_spec(d, nt)
            base["moe"] = moe.moe_spec(cfg)
        elif cfg.family == "encdec":
            base["ln2"] = norm_spec(d, nt)
            base["cross"] = attn.attn_spec(cfg)
            base["ln3"] = norm_spec(d, nt)
            base["mlp"] = mlp_spec(d, cfg.d_ff, cfg.act)
        else:
            base["ln2"] = norm_spec(d, nt)
            base["mlp"] = mlp_spec(d, cfg.d_ff, cfg.act)
        return base

    def _cross_layer_spec(self) -> Dict[str, Pytree]:
        cfg = self.cfg
        d = cfg.d_model
        return {
            "ln1": norm_spec(d, cfg.norm_type),
            "cross": attn.attn_spec(cfg),
            "ln2": norm_spec(d, cfg.norm_type),
            "mlp": mlp_spec(d, cfg.d_ff, cfg.act),
            "gate_attn": ParamSpec((1,), (None,), "zeros"),
            "gate_mlp": ParamSpec((1,), (None,), "zeros"),
        }

    def spec(self) -> Dict[str, Pytree]:
        cfg = self.cfg
        out: Dict[str, Pytree] = {
            "tok_embed": embed_spec(cfg.vocab_size, cfg.d_model),
            "final_norm": norm_spec(cfg.d_model, cfg.norm_type),
        }
        if not cfg.tie_embeddings:
            out["unembed"] = unembed_spec(cfg.d_model, cfg.vocab_size)
        if cfg.family == "vlm":
            ne = cfg.cross_attn_every
            n_groups = cfg.n_layers // ne
            per_group = ne - 1
            out["layers"] = _stack_spec(
                _stack_spec(self._layer_spec_dense_like(), per_group), n_groups)
            out["cross_layers"] = _stack_spec(self._cross_layer_spec(), n_groups)
        else:
            out["layers"] = _stack_spec(self._layer_spec(), cfg.n_layers)
        if cfg.family == "encdec":
            enc_layer = {
                "ln1": norm_spec(cfg.d_model, cfg.norm_type),
                "attn": attn.attn_spec(cfg),
                "ln2": norm_spec(cfg.d_model, cfg.norm_type),
                "mlp": mlp_spec(cfg.d_model, cfg.d_ff, cfg.act),
            }
            out["encoder"] = {
                "layers": _stack_spec(enc_layer, cfg.n_encoder_layers),
                "final_norm": norm_spec(cfg.d_model, cfg.norm_type),
            }
        return out

    def _layer_spec_dense_like(self) -> Dict[str, Pytree]:
        cfg = self.cfg
        return {
            "ln1": norm_spec(cfg.d_model, cfg.norm_type),
            "attn": attn.attn_spec(cfg),
            "ln2": norm_spec(cfg.d_model, cfg.norm_type),
            "mlp": mlp_spec(cfg.d_model, cfg.d_ff, cfg.act),
        }

    def init(self, key: jax.Array, dtype=jnp.float32) -> Pytree:
        return init_params(self.spec(), key, dtype)

    def abstract(self, dtype=jnp.bfloat16) -> Pytree:
        return abstract_params(self.spec(), dtype)

    def partition_specs(self, rules=None, mesh_shape=None) -> Pytree:
        return partition_specs(self.spec(), rules, mesh_shape=mesh_shape)

    # ---------------------------------------------------------- embeddings --
    def _embed(self, params, tokens, start_pos) -> jax.Array:
        cfg = self.cfg
        x = jnp.take(params["tok_embed"], tokens, axis=0)
        if not cfg.use_rope:
            s = tokens.shape[1]
            if jnp.ndim(start_pos) == 1:          # per-row ragged positions
                pos = start_pos[:, None] + jnp.arange(s)[None, :]
                x = x + sinusoidal_positions(pos, cfg.d_model).astype(x.dtype)
            else:
                pos = start_pos + jnp.arange(s)
                x = x + sinusoidal_positions(
                    pos, cfg.d_model)[None].astype(x.dtype)
        return constrain(x, ("act_batch", "act_seq", "act_embed"))

    def _unembed(self, params, x) -> jax.Array:
        with jax.named_scope("lm_head"):
            if self.cfg.tie_embeddings:
                logits = jnp.einsum("...d,vd->...v", x, params["tok_embed"])
            else:
                logits = jnp.einsum("...d,dv->...v", x, params["unembed"])
            return constrain(logits, ("act_batch", "act_seq", "act_vocab"))

    # --------------------------------------------------------- train blocks --
    def _block_train(self, x, lp, positions, extras) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        aux = {}
        h = apply_norm(x, lp["ln1"], cfg.norm_type, cfg.rmsnorm_eps)
        if cfg.family == "ssm":
            x = x + mamba2.apply_mamba(h, lp["mixer"], cfg)
            return x, aux
        if cfg.family == "hybrid":
            a = attn.self_attention(h, lp["attn"], cfg, positions,
                                    window=cfg.sliding_window)
            m = mamba2.apply_mamba(h, lp["mamba"], cfg)
            x = x + 0.5 * (a + m)
        else:
            x = x + attn.self_attention(h, lp["attn"], cfg, positions,
                                        window=cfg.sliding_window)
        if cfg.family == "encdec":
            h = apply_norm(x, lp["ln2"], cfg.norm_type, cfg.rmsnorm_eps)
            x = x + attn.cross_attention(h, extras["enc"], lp["cross"], cfg)
            h = apply_norm(x, lp["ln3"], cfg.norm_type, cfg.rmsnorm_eps)
            x = x + apply_mlp(h, lp["mlp"], cfg.act)
        elif cfg.family == "moe":
            h = apply_norm(x, lp["ln2"], cfg.norm_type, cfg.rmsnorm_eps)
            y, moe_aux = moe.apply_moe(h, lp["moe"], cfg)
            x = x + y
            aux = moe_aux
        else:
            h = apply_norm(x, lp["ln2"], cfg.norm_type, cfg.rmsnorm_eps)
            x = x + apply_mlp(h, lp["mlp"], cfg.act)
        return constrain(x, ("act_batch", "act_seq", "act_embed")), aux

    def _cross_block_train(self, x, lp, src) -> jax.Array:
        cfg = self.cfg
        h = apply_norm(x, lp["ln1"], cfg.norm_type, cfg.rmsnorm_eps)
        x = x + jnp.tanh(lp["gate_attn"]) * attn.cross_attention(
            h, src, lp["cross"], cfg)
        h = apply_norm(x, lp["ln2"], cfg.norm_type, cfg.rmsnorm_eps)
        x = x + jnp.tanh(lp["gate_mlp"]) * apply_mlp(h, lp["mlp"], cfg.act)
        return x

    # -------------------------------------------------------------- encode --
    def encode(self, params, encoder_embeds: jax.Array) -> jax.Array:
        """Whisper encoder over precomputed audio-frame embeddings (stub
        frontend per DESIGN.md carve-out)."""
        cfg = self.cfg
        enc = params["encoder"]
        s = encoder_embeds.shape[1]
        x = encoder_embeds + sinusoidal_positions(
            jnp.arange(s), cfg.d_model)[None].astype(encoder_embeds.dtype)

        def step(x, lp):
            h = apply_norm(x, lp["ln1"], cfg.norm_type, cfg.rmsnorm_eps)
            # bidirectional self-attention
            q, k, v = attn.qkv(h, lp["attn"])
            o = attn.sdpa(q, attn._repeat_kv(k, cfg.n_heads // cfg.n_kv_heads),
                          attn._repeat_kv(v, cfg.n_heads // cfg.n_kv_heads),
                          None)
            x = x + attn.out_proj(o, lp["attn"])
            h = apply_norm(x, lp["ln2"], cfg.norm_type, cfg.rmsnorm_eps)
            x = x + apply_mlp(h, lp["mlp"], cfg.act)
            return x, None

        x, _ = jax.lax.scan(step, x, enc["layers"])
        return apply_norm(x, enc["final_norm"], cfg.norm_type, cfg.rmsnorm_eps)

    # -------------------------------------------------------------- forward --
    def forward(self, params, tokens, image_embeds=None, encoder_embeds=None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Full-sequence causal forward (training path).  Returns
        (logits (B,S,V), aux losses)."""
        cfg = self.cfg
        b, s = tokens.shape
        x = self._embed(params, tokens, jnp.zeros((), jnp.int32))
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        extras = {}
        if cfg.family == "encdec":
            extras["enc"] = self.encode(params, encoder_embeds)

        # Activation checkpointing: recompute each layer in the backward
        # pass instead of saving its internals — this is what bounds
        # train_4k temp memory on the production mesh (EXPERIMENTS.md §Perf
        # quantifies the effect).
        maybe_remat = jax.checkpoint if cfg.remat else (lambda f: f)

        if cfg.family == "vlm":
            @maybe_remat
            def group(x, gp):
                lp_group, cp = gp

                def inner(x, lp):
                    y, _ = self._block_train(x, lp, positions, extras)
                    return y, None
                x, _ = jax.lax.scan(inner, x, lp_group)
                x = self._cross_block_train(x, cp, image_embeds)
                return x, None
            x, _ = jax.lax.scan(group, x,
                                (params["layers"], params["cross_layers"]))
            aux = {}
        else:
            @maybe_remat
            def step(x, lp):
                y, a = self._block_train(x, lp, positions, extras)
                return y, a
            x, auxs = jax.lax.scan(step, x, params["layers"])
            aux = {k: jnp.mean(v) for k, v in auxs.items()} if auxs else {}

        x = apply_norm(x, params["final_norm"], cfg.norm_type, cfg.rmsnorm_eps)
        return self._unembed(params, x), aux

    # ------------------------------------------------------- decode support --
    def init_state(self, batch: int, capacity: int, dtype=jnp.float32,
                   ring: bool = False, n_cross_src: int = 0) -> DecodeState:
        return make_decode_state(self.cfg, batch, capacity, dtype, ring,
                                 n_cross_src)

    def prep_cross(self, params, state: DecodeState, src: jax.Array
                   ) -> DecodeState:
        """Precompute per-layer cross-attention KV from image/encoder states
        and store in the decode state (done once at prefill)."""
        cfg = self.cfg
        cl = (params["cross_layers"] if cfg.family == "vlm"
              else params["layers"])

        def one(lp):
            return attn.cross_kv(src, lp["cross"])
        ck, cv = jax.vmap(one)(cl)
        return dataclasses.replace(state, cross_k=ck.astype(state.cross_k.dtype),
                                   cross_v=cv.astype(state.cross_v.dtype))

    # ---------------------------------------------------------- layer scan --
    def _scan_layers(self, params, x, state: DecodeState, attend, mix
                     ) -> Tuple[jax.Array, DecodeState]:
        """Every layer over ``x``, threading the decode state (``pos``
        left to the caller).  ``attend(h, p, k, v, layer)`` is the cached
        self-attention of one layer, given the WHOLE stacked caches
        ``(L, B, C, K*hd)``: they ride in the scan's carry, so each layer
        writes only its new tokens' slots and the cache is never
        restacked.  ``mix(h, p, conv, ssm) -> (y, (conv, ssm))`` is one
        layer's Mamba mixer; its small per-layer states stay scanned
        inputs and outputs."""
        cfg = self.cfg

        def norm(x, p):
            return apply_norm(x, p, cfg.norm_type, cfg.rmsnorm_eps)

        if cfg.family == "ssm":
            def ssm_step(x, xs):
                lp, conv, ssm = xs
                y, (conv, ssm) = mix(norm(x, lp["ln1"]), lp["mixer"],
                                     conv, ssm)
                return x + y, (conv, ssm)
            x, (conv, ssm) = jax.lax.scan(
                ssm_step, x, (params["layers"], state.conv, state.ssm))
            return x, dataclasses.replace(state, conv=conv, ssm=ssm)

        if cfg.family == "vlm":
            ng, pg = params["layers"]["attn"]["wq"].shape[:2]

            def group(carry, xs):
                g, lp_g, cp, ckl, cvl = xs

                def inner(carry, ys):
                    x, k, v = carry
                    j, lp = ys
                    o, k, v = attend(norm(x, lp["ln1"]), lp["attn"], k, v,
                                     g * pg + j)
                    x = x + o
                    x = x + apply_mlp(norm(x, lp["ln2"]), lp["mlp"], cfg.act)
                    return (x, k, v), None
                (x, k, v), _ = jax.lax.scan(inner, carry,
                                            (jnp.arange(pg), lp_g))
                x = x + jnp.tanh(cp["gate_attn"]) * attn.cross_attention(
                    norm(x, cp["ln1"]), None, cp["cross"], cfg,
                    cached_kv=(ckl, cvl))
                x = x + jnp.tanh(cp["gate_mlp"]) * apply_mlp(
                    norm(x, cp["ln2"]), cp["mlp"], cfg.act)
                return (x, k, v), None

            (x, k, v), _ = jax.lax.scan(
                group, (x, state.k, state.v),
                (jnp.arange(ng), params["layers"], params["cross_layers"],
                 state.cross_k, state.cross_v))
            return x, dataclasses.replace(state, k=k, v=v)

        # dense, moe, encdec and hybrid share one scan
        if cfg.family == "encdec":
            extra = (state.cross_k, state.cross_v)
        elif cfg.family == "hybrid":
            extra = (state.conv, state.ssm)
        else:
            extra = ()

        def step(carry, xs):
            x, k, v = carry
            i, lp, extra = xs
            h = norm(x, lp["ln1"])
            o, k, v = attend(h, lp["attn"], k, v, i)
            if cfg.family == "hybrid":
                m, extra = mix(h, lp["mamba"], *extra)
                x = x + 0.5 * (o + m)
            else:
                x = x + o
            if cfg.family == "encdec":
                x = x + attn.cross_attention(norm(x, lp["ln2"]), None,
                                             lp["cross"], cfg,
                                             cached_kv=extra)
                x = x + apply_mlp(norm(x, lp["ln3"]), lp["mlp"], cfg.act)
            elif cfg.family == "moe":
                y, _ = moe.apply_moe(norm(x, lp["ln2"]), lp["moe"], cfg)
                x = x + y
            else:
                x = x + apply_mlp(norm(x, lp["ln2"]), lp["mlp"], cfg.act)
            return (x, k, v), extra if cfg.family == "hybrid" else None

        n = state.k.shape[0]
        (x, k, v), extra = jax.lax.scan(
            step, (x, state.k, state.v),
            (jnp.arange(n), params["layers"], extra))
        new_state = dataclasses.replace(state, k=k, v=v)
        if cfg.family == "hybrid":
            new_state = dataclasses.replace(new_state, conv=extra[0],
                                            ssm=extra[1])
        return x, new_state

    # ----------------------------------------------------- prefill / extend --
    def prefill(self, params, tokens, state: DecodeState,
                width: Optional[int] = None
                ) -> Tuple[jax.Array, DecodeState]:
        """Process S tokens starting at state.pos (chunked prefill / extend).
        Returns (logits (B,S,V), new state).  Used for prompts, for
        SpecReason verification passes, and for accepting speculated steps
        into the base model's cache.  ``state.pos`` may be a scalar or a
        (B,) vector (ragged rows — continuous batching); the attention
        layer handles per-row scatter/masking.  ``width`` (static, default
        the whole capacity) is how many leading cache slots attention
        reads; the caller keeps every real token below it."""
        cfg = self.cfg
        s = tokens.shape[1]
        start = state.pos
        x = self._embed(params, tokens, start)
        width = width or state.capacity

        def attend(h, p, k, v, layer):
            return attn.prefill_self_attention(h, p, cfg, k, v, layer, start,
                                               width, cfg.sliding_window)

        def mix(h, p, conv, ssm):
            return mamba2.apply_mamba(h, p, cfg, state=(conv, ssm),
                                      return_state=True)

        x, new_state = self._scan_layers(params, x, state, attend, mix)
        x = apply_norm(x, params["final_norm"], cfg.norm_type, cfg.rmsnorm_eps)
        return (self._unembed(params, x),
                dataclasses.replace(new_state, pos=start + s))

    # --------------------------------------------------------------- decode --
    def decode_step(self, params, state: DecodeState, tokens,
                    width: Optional[int] = None
                    ) -> Tuple[jax.Array, DecodeState]:
        """One-token decode.  tokens: (B, 1).  Returns (logits (B,V), state).
        ``width`` as in :meth:`prefill` (a ring buffer takes the whole
        capacity)."""
        cfg = self.cfg
        pos = state.pos
        x = self._embed(params, tokens, pos)
        width = width or state.capacity

        def attend(h, p, k, v, layer):
            return attn.decode_self_attention(h, p, cfg, k, v, layer, pos,
                                              width, ring=state.ring)

        def mix(h, p, conv, ssm):
            return mamba2.apply_mamba_decode(h, p, cfg, (conv, ssm))

        x, new_state = self._scan_layers(params, x, state, attend, mix)
        x = apply_norm(x, params["final_norm"], cfg.norm_type, cfg.rmsnorm_eps)
        logits = self._unembed(params, x)[:, 0, :]
        return logits, dataclasses.replace(new_state, pos=pos + 1)


@functools.lru_cache(maxsize=64)
def _cached_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def get_model(cfg: ModelConfig) -> Model:
    return _cached_model(cfg)

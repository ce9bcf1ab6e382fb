"""Parameter-spec machinery + elementary layers shared by all families.

Every model in this framework is a *pure function* over a params pytree.
Parameters are declared once as :class:`ParamSpec` trees which can be

* materialized into real arrays (``init_params``),
* turned into ``jax.ShapeDtypeStruct``s for allocation-free lowering
  (``abstract_params`` — this is what the multi-pod dry-run uses), or
* mapped to ``PartitionSpec``s through logical-axis rules
  (``partition_specs``).

Logical axes vocabulary:
  "layers"     stacked layer dim (scan over layers)
  "embed"      d_model
  "vocab"      vocabulary
  "heads"      query heads            -> "model"
  "kv_heads"   key/value heads        -> "model"
  "head_dim"   per-head dim
  "mlp"        ffn hidden             -> "model"
  "experts"    MoE experts            -> "model"
  "ssm_inner"  mamba d_inner          -> "model"
  "ssm_heads"  mamba heads            -> "model"
  "ssm_state"  SSD state dim
  "conv"       conv kernel taps
  None         replicated
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding import constrain

Pytree = Any

# Default logical-axis -> mesh-axis rules (baseline tensor parallelism).
DEFAULT_RULES: Dict[str, Any] = {
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "vocab": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "embed": None,
    "layers": None,
    "conv": None,
}

# FSDP variant: additionally shard the replicated "embed" dim of weights over
# the data axis (ZeRO-3-like; XLA inserts all-gathers at use sites).
FSDP_RULES = dict(DEFAULT_RULES, embed="data")

# Exact-TP variant (sharded serving): shard q/k/v heads and the ffn
# hidden, keep the unembed replicated so sampling sees replicated logits.
# "heads"/"mlp" also shard wo/w_down, on their contracting dims: the
# compiler then all-reduces those dots, so TP>1 matches TP=1 bitwise only
# on the toy configs of tests/test_tp_serving.py (DESIGN.md §Sharded
# serving).  Engines must check that tp divides n_heads/n_kv_heads: the
# head_dim FALLBACK would shard a contraction dim.
EXACT_TP_RULES = dict(DEFAULT_RULES, vocab=None, experts=None,
                      ssm_inner=None, ssm_heads=None)


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"         # normal | zeros | ones | scaled | uniform_dt | arange_log
    scale: float = 1.0           # stddev multiplier for normal/scaled
    fan_in_axis: Optional[int] = None  # for "scaled": 1/sqrt(shape[fan_in_axis])

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _materialize(spec: ParamSpec, key: jax.Array, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "arange_log":
        # Mamba A_log init: log of 1..H
        h = spec.shape[-1]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
                                spec.shape).astype(dtype)
    if spec.init == "uniform_dt":
        # Mamba dt_bias init: softplus^-1 of dt ~ U[dt_min, dt_max]
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.clip(dt, 1e-4, None)
        inv = dt + jnp.log(-jnp.expm1(-dt))
        return inv.astype(dtype)
    std = spec.scale
    if spec.init == "scaled":
        fan = spec.shape[spec.fan_in_axis if spec.fan_in_axis is not None else 0]
        std = spec.scale / math.sqrt(max(fan, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(spec_tree: Pytree, key: jax.Array, dtype=jnp.float32) -> Pytree:
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return [_materialize(s, k, dtype) for s, k in zip(leaves, keys)]

    # A narrower dtype is drawn in float32 and cast inside one program, so
    # no float32 draw is held in device memory (eagerly, phi3's largest
    # leaf holds 6.4 GB of float32 beside the 1.6 GB it becomes).  Float32
    # stays eager: fusing changes its bits on XLA:CPU, and the trained
    # testbed starts from them.
    if jnp.dtype(dtype) == jnp.float32:
        return jax.tree.unflatten(treedef, draw(key))
    return jax.tree.unflatten(treedef, jax.jit(draw)(key))


def abstract_params(spec_tree: Pytree, dtype=jnp.bfloat16) -> Pytree:
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
                        spec_tree, is_leaf=is_spec)


# When a preferred logical axis is not divisible by its mesh axis, try the
# fallback dim of the same tensor instead (e.g. GQA kv_heads=8 on a 16-way
# model axis -> shard head_dim: row-parallel attention, contraction over the
# sharded dim becomes a partial-sum all-reduce under GSPMD).
FALLBACK_AXES: Dict[str, str] = {
    "heads": "head_dim",
    "kv_heads": "head_dim",
    "ssm_heads": "ssm_state",
}


def _axis_size(m, mesh_shape: Optional[Dict[str, int]]) -> int:
    if mesh_shape is None:
        return 1
    if isinstance(m, (tuple, list)):
        n = 1
        for a in m:
            n *= mesh_shape.get(a, 1)
        return n
    return mesh_shape.get(m, 1)


def partition_specs(spec_tree: Pytree, rules: Optional[Dict[str, Any]] = None,
                    mesh_axes: Sequence[str] = ("data", "model", "pod"),
                    mesh_shape: Optional[Dict[str, int]] = None) -> Pytree:
    """Map ParamSpec logical axes to PartitionSpecs.

    mesh_shape (axis name -> size) enables divisibility checks: dims that
    do not divide their mesh axis are replicated, with a per-tensor
    fallback (FALLBACK_AXES) tried first."""
    rules = dict(DEFAULT_RULES if rules is None else rules)

    def one(s: ParamSpec) -> P:
        out: list = []
        for ax, dim in zip(s.axes, s.shape):
            m = rules.get(ax) if ax is not None else None
            if m is not None and not all(
                    a in mesh_axes for a in
                    (m if isinstance(m, (tuple, list)) else (m,))):
                m = None
            if m is not None and dim % _axis_size(m, mesh_shape) != 0:
                m = "__fallback__" if FALLBACK_AXES.get(ax) else None
            out.append(m)
        # resolve fallbacks: move the sharding onto the fallback dim
        for i, m in enumerate(out):
            if m != "__fallback__":
                continue
            out[i] = None
            target = FALLBACK_AXES[s.axes[i]]
            mm = rules.get(s.axes[i])
            for j, ax in enumerate(s.axes):
                if ax == target and out[j] is None \
                        and s.shape[j] % _axis_size(mm, mesh_shape) == 0:
                    out[j] = mm
                    break
        # never map the same mesh axis twice in one spec
        seen = set()
        final = []
        for m in out:
            key = tuple(m) if isinstance(m, (tuple, list)) else m
            if m is not None and key in seen:
                m = None
            if m is not None:
                seen.add(key)
            final.append(m)
        return P(*final)

    return jax.tree.map(one, spec_tree, is_leaf=is_spec)


def param_count(spec_tree: Pytree) -> int:
    leaves = jax.tree.leaves(spec_tree, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


# ---------------------------------------------------------------------------
# Elementary layers (functional)
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def apply_norm(x: jax.Array, p: Dict[str, jax.Array], norm_type: str,
               eps: float) -> jax.Array:
    if norm_type == "layernorm":
        return layernorm(x, p["scale"], p["bias"], eps)
    return rmsnorm(x, p["scale"], eps)


def norm_spec(d: int, norm_type: str) -> Dict[str, ParamSpec]:
    spec = {"scale": ParamSpec((d,), ("embed",), "ones")}
    if norm_type == "layernorm":
        spec["bias"] = ParamSpec((d,), ("embed",), "zeros")
    return spec


# -- rotary position embeddings ----------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., seq, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings. positions: (...,) -> (..., d)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# -- MLPs ---------------------------------------------------------------------

def mlp_spec(d: int, ff: int, act: str) -> Dict[str, ParamSpec]:
    if act == "swiglu":
        return {
            "w_gate": ParamSpec((d, ff), ("embed", "mlp"), "scaled", 1.0, 0),
            "w_up": ParamSpec((d, ff), ("embed", "mlp"), "scaled", 1.0, 0),
            "w_down": ParamSpec((ff, d), ("mlp", "embed"), "scaled", 1.0, 0),
        }
    return {
        "w_in": ParamSpec((d, ff), ("embed", "mlp"), "scaled", 1.0, 0),
        "b_in": ParamSpec((ff,), ("mlp",), "zeros"),
        "w_out": ParamSpec((ff, d), ("mlp", "embed"), "scaled", 1.0, 0),
        "b_out": ParamSpec((d,), ("embed",), "zeros"),
    }


def _constrain_hidden(h: jax.Array) -> jax.Array:
    # "act_mlp_hidden" is the ffn hidden dim at the down-projection
    # contraction boundary: default rules keep it sharded on the model
    # axis (partial-sum dot), the exact-TP serving rules map it to None so
    # the hidden is all-gathered first and the down-proj dot runs with a
    # single-device reduction order (bitwise-identical activations).
    axes = ("act_batch",) + (None,) * (h.ndim - 2) + ("act_mlp_hidden",)
    return constrain(h, axes)


def apply_mlp(x: jax.Array, p: Dict[str, jax.Array], act: str) -> jax.Array:
    with jax.named_scope("mlp"):
        return _mlp(x, p, act)


def _mlp(x: jax.Array, p: Dict[str, jax.Array], act: str) -> jax.Array:
    if act == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        u = jnp.einsum("...d,df->...f", x, p["w_up"])
        h = _constrain_hidden(jax.nn.silu(g) * u)
        return jnp.einsum("...f,fd->...d", h, p["w_down"])
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w_in"]) + p["b_in"])
    return jnp.einsum("...f,fd->...d", _constrain_hidden(h),
                      p["w_out"]) + p["b_out"]


# -- embeddings ----------------------------------------------------------------

def embed_spec(vocab: int, d: int) -> ParamSpec:
    return ParamSpec((vocab, d), ("vocab", "embed"), "normal", 0.02)


def unembed_spec(d: int, vocab: int) -> ParamSpec:
    return ParamSpec((d, vocab), ("embed", "vocab"), "scaled", 1.0, 0)

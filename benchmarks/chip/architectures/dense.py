"""A dense decoder: RMSNorm or LayerNorm, RoPE, grouped-query attention
with no biases, SwiGLU or biased tanh-GELU MLP, untied output head.

A configuration names its architecture (``"architecture": "dense"``) and
the harness loads ``architectures/<architecture>.py``.  The module owns
everything that depends on the architecture; the harness, the check and
the readers call only these names:

  dims(config)               the sizes read from the configuration: an
                             object with ``n_layers`` and ``jnp_dtype``,
                             handed to every function below and to the
                             configuration's plain reference
  model_config(dims, n_layers=None, suffix="")
                             the serving program's ``ModelConfig``
  layout(dims)               leaf shapes of the parameter tree, keyed as
                             the program keys them (weights.py draws it)
  drafter_params(params, n_layers)
                             the drafter's tree, built from the base's
  tokens_flops(dims, a, b)   forward FLOPs of the tokens at positions
                             [a, b) (``mfu``)
  SCOPES                     the program's device name scopes for this
                             architecture (spans.py's ``device_by_scope``)

Another architecture may import this module (``from architectures import
dense``) and override only what differs.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

SCOPES = ("attn", "kv_write", "mlp", "lm_head")

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, read from a configuration file."""
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str            # "swiglu" | "gelu"
    norm: str           # "rmsnorm" | "layernorm"
    eps: float
    rope_theta: float
    window: int         # 0 = full attention
    dtype: str

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        act = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}[cfg["hidden_act"]]
        return cls(name=cfg["name"], n_layers=cfg["num_hidden_layers"],
                   d_model=d, n_heads=h,
                   n_kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim", d // h),
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   act=act, norm=cfg["norm_type"],
                   eps=cfg.get("rms_norm_eps", cfg.get("norm_epsilon")),
                   rope_theta=float(cfg["rope_theta"]),
                   window=int(cfg.get("sliding_window") or 0),
                   dtype=cfg["dtype"])

    @property
    def jnp_dtype(self):
        return _DTYPES[self.dtype]


def dims(config: Dict) -> Dims:
    return Dims.from_config(config)


def model_config(dims: Dims, n_layers: Optional[int] = None,
                 suffix: str = ""):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=dims.name + suffix, family="dense",
        n_layers=n_layers or dims.n_layers, d_model=dims.d_model,
        n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads,
        head_dim=dims.head_dim, d_ff=dims.d_ff, vocab_size=dims.vocab,
        rmsnorm_eps=dims.eps, norm_type=dims.norm, act=dims.act,
        rope_theta=dims.rope_theta, sliding_window=dims.window).validate()


# ------------------------------------------------------------ parameters

def _norm_shapes(dims: Dims, lead=()) -> Dict:
    out = {"scale": lead + (dims.d_model,)}
    if dims.norm == "layernorm":
        out["bias"] = lead + (dims.d_model,)
    return out


def layout(dims: Dims) -> Dict:
    """Leaf shapes of the parameter tree, keyed as the program keys them."""
    L, d, h, k, hd, ff = (dims.n_layers, dims.d_model, dims.n_heads,
                          dims.n_kv_heads, dims.head_dim, dims.d_ff)
    if dims.act == "swiglu":
        mlp = {"w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    else:
        mlp = {"w_in": (L, d, ff), "b_in": (L, ff), "w_out": (L, ff, d),
               "b_out": (L, d)}
    return {
        "tok_embed": (dims.vocab, d),
        "final_norm": _norm_shapes(dims),
        "unembed": (d, dims.vocab),
        "layers": {
            "ln1": _norm_shapes(dims, (L,)),
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, k, hd),
                     "wv": (L, d, k, hd), "wo": (L, h, hd, d)},
            "ln2": _norm_shapes(dims, (L,)),
            "mlp": mlp,
        },
    }


def drafter_params(params: Dict, n_layers: int) -> Dict:
    """An early-exit drafter: the base's first ``n_layers`` layers (a
    copy of their slice) over the base's own embedding, final norm and
    output-head buffers (shared, not copied)."""
    return {"tok_embed": params["tok_embed"],
            "final_norm": params["final_norm"],
            "unembed": params["unembed"],
            "layers": jax.tree.map(lambda x: x[:n_layers], params["layers"])}


# ----------------------------------------------------------------- FLOPs
#
# What vanilla decoding with the base model would spend on a token at
# context position ``t`` (0-based): two FLOPs per multiply-add of every
# non-embedding weight and of the output head, plus attention's two
# products (q.k and p.v) over the ``min(t + 1, window)`` keys it sees.
# Embedding lookups, norms and softmax are not counted.  It does not
# depend on how the program computes: drafts, rejected work and padding
# add nothing.

def matmul_params(dims: Dims) -> int:
    """Weights multiplied per token: attention, MLP and output head."""
    d, hd = dims.d_model, dims.head_dim
    attn = d * dims.n_heads * hd * 2 + d * dims.n_kv_heads * hd * 2
    mlp = (3 if dims.act == "swiglu" else 2) * d * dims.d_ff
    return dims.n_layers * (attn + mlp) + d * dims.vocab


def attn_flops_per_key(dims: Dims) -> int:
    return 4 * dims.n_layers * dims.n_heads * dims.head_dim


def keys_sum(window: int, a: int, b: int) -> int:
    """Sum over positions t in [a, b) of the keys each attends to under a
    sliding ``window`` (0: full attention)."""
    w = window or (1 << 62)
    total = 0
    lo, hi = a, min(b, w)              # t < window: t + 1 keys
    if hi > lo:
        total += (lo + 1 + hi) * (hi - lo) // 2
    lo = max(a, w)                     # t >= window: window keys
    if b > lo:
        total += w * (b - lo)
    return total


def tokens_flops(dims: Dims, a: int, b: int) -> int:
    """Forward FLOPs of the tokens at positions [a, b)."""
    if b <= a:
        return 0
    return (2 * matmul_params(dims) * (b - a)
            + attn_flops_per_key(dims) * keys_sum(dims.window, a, b))

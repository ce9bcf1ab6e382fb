"""Seeded weights for a dense decoder configuration, made on the device.

The benchmark owns its weights: one jitted call draws every leaf from
``--seed`` in the dtype it is served in, in the parameter layout the
serving program reads (``tok_embed``, ``final_norm``, ``unembed`` and the
layer-stacked ``layers`` tree).  The plain reference draws the same tree
again from the same seed once the program's state is freed, so it takes
nothing the program has made.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

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


def _draw(path: str, shape, key, dtype):
    """One leaf: unit-variance activations through every matrix, norm
    scales near 1 and small biases, so that a wrong scale, bias or
    transpose changes the logits."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, dtype)).astype(dtype)
    if leaf.startswith("b"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    if leaf == "tok_embed":
        return jax.random.normal(key, shape, dtype)
    # fan-in: every axis the matrix contracts over
    if leaf in ("wq", "wk", "wv"):
        fan = shape[-3]
    elif leaf == "wo":
        fan = shape[-3] * shape[-2]
    else:
        fan = shape[-2]
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        1.0 / math.sqrt(fan), dtype)


def _paths(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from _paths(v, p)
        else:
            yield p, v


def _set(tree, path, value):
    *head, last = path.split("/")
    for k in head:
        tree = tree.setdefault(k, {})
    tree[last] = value


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a whole number of any size (seeds may pass 32
    bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_params(dims: Dims, seed: int) -> Dict:
    """The whole tree, drawn on the default device in one jitted call."""
    shapes = list(_paths(layout(dims)))
    dtype = dims.jnp_dtype

    def draw(key):
        out: Dict = {}
        for i, (path, shape) in enumerate(shapes):
            _set(out, path, _draw(path, shape, jax.random.fold_in(key, i),
                                  dtype))
        return out

    return jax.jit(draw)(jax.random.fold_in(seed_key(seed), 0x5EED))


def drafter_params(params: Dict, n_layers: int) -> Dict:
    """An early-exit drafter: the base's first ``n_layers`` layers (a
    copy of their slice) over the base's own embedding, final norm and
    output-head buffers (shared, not copied)."""
    return {"tok_embed": params["tok_embed"],
            "final_norm": params["final_norm"],
            "unembed": params["unembed"],
            "layers": jax.tree.map(lambda x: x[:n_layers], params["layers"])}

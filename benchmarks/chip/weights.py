"""Seeded weights, made on the device.

The benchmark owns its weights: one jitted call draws every leaf of the
parameter layout a configuration's architecture module gives (``layout``
in ``architectures/<name>.py``) from a seed, in the dtype it is served
in.  Leaves are drawn in the order of their sorted paths, each from the
seed's key folded with its index, by a rule on the leaf's name
(``_draw``).  The plain reference draws the same tree again from the
same seed once the program's state is freed, so it takes nothing the
program has made.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


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


def make_params(layout: Dict, dtype, seed: int) -> Dict:
    """The whole tree of leaf shapes ``layout``, drawn in ``dtype`` on
    the default device in one jitted call."""
    shapes = list(_paths(layout))

    def draw(key):
        out: Dict = {}
        for i, (path, shape) in enumerate(shapes):
            _set(out, path, _draw(path, shape, jax.random.fold_in(key, i),
                                  dtype))
        return out

    return jax.jit(draw)(jax.random.fold_in(seed_key(seed), 0x5EED))


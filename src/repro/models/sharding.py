"""Activation-sharding rules as an ambient context.

Model code calls ``constrain(x, ("act_batch", None, "act_heads", None))``
with *logical* activation axes; the launcher installs a mapping from logical
axes to mesh axes for the mesh/shape at hand.  Outside any context (CPU
tests, single device) ``constrain`` is a no-op, keeping the model code
mesh-agnostic.  Inside a context the constraint is applied or the call
raises: a spec the mesh cannot take is an error, never an unconstrained
array.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()

# Baseline logical activation axis -> mesh axis rules.
def default_activation_rules(data_axes=("data",), model_axis="model",
                             shard_batch: bool = True) -> Dict[str, Any]:
    batch = tuple(data_axes) if shard_batch else None
    return {
        "act_batch": batch,      # batch / token-group dims
        "act_seq": None,         # sequence (baseline: unsharded)
        "act_embed": None,       # d_model
        "act_heads": model_axis, # attention heads
        "act_kv": model_axis,    # kv heads
        "act_mlp": model_axis,   # ffn hidden
        "act_experts": model_axis,
        "act_vocab": model_axis,
        "act_ssm": model_axis,   # mamba inner / heads
        # decode KV-cache sequence dim: "model" in sequence-parallel
        # flash-decode mode (when kv heads don't divide the model axis),
        # None otherwise — set per-shape by the launcher.
        "act_cache_seq": None,
        # contraction-boundary dims (attention heads entering out_proj,
        # ffn hidden entering the down-projection): kept sharded here
        # (partial-sum dot + psum, the cheap baseline); the exact-TP
        # serving rules map them to None to force the all-gather BEFORE
        # the contraction (bitwise-identical to single-device).
        "act_out_heads": model_axis,
        "act_mlp_hidden": model_axis,
    }


def exact_tp_activation_rules(model_axis: str = "model") -> Dict[str, Any]:
    """Activation rules for BIT-EXACT tensor-parallel serving.

    Only *output* (non-contraction) dims stay sharded: attention math runs
    per-kv-head on the model axis and the ffn hidden is computed sharded,
    but every tensor entering a contraction (`act_out_heads`,
    `act_mlp_hidden`) is constrained replicated first.  A column slice of
    a dot is computed with the same reduction order as the unsharded dot,
    and an all-gather moves bits without arithmetic — so every device
    holds bitwise the TP=1 activations at layer boundaries, which is what
    lets TP>1 serving claim *token identity* (not just tolerance) against
    the single-device path (DESIGN.md §Sharded serving).  The price is an
    all-gather + replicated second GEMM per block instead of Megatron's
    row-parallel psum — the documented exactness/efficiency trade."""
    rules = default_activation_rules(data_axes=(), model_axis=model_axis,
                                     shard_batch=False)
    rules["act_out_heads"] = None     # gather heads before out_proj
    rules["act_mlp_hidden"] = None    # gather hidden before down-proj
    rules["act_vocab"] = None         # logits replicated (exact sampling)
    return rules


@contextlib.contextmanager
def activation_sharding(rules: Optional[Dict[str, Any]]):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def active_rules() -> Optional[Dict[str, Any]]:
    return getattr(_state, "rules", None)


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    rules = active_rules()
    if rules is None:
        return x
    spec = []
    used = set()
    for a in axes:
        m = rules.get(a) if a is not None else None
        key = tuple(m) if isinstance(m, (list, tuple)) else m
        if m is not None and key in used:
            m = None
        elif m is not None:
            used.add(key)
        spec.append(m)
    return jax.lax.with_sharding_constraint(x, P(*spec))

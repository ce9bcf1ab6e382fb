"""Tensor-parallel serving context: one object carrying the mesh, the
exact-TP sharding rules, and the placement helpers every serving layer
shares.

Design (DESIGN.md §Sharded serving): serving TP aims to be *bit-exact*
against the single-device path — the scheduler's token-identity
guarantees (batched vs sequential, spec-decode vs plain decode, cached
vs uncached prefixes) are all transitive through the engine.  The rules
shard q/k/v over heads / kv-heads and the mlp up/gate over the ffn
hidden dim ("model" axis), keep embed/unembed replicated, and ask for the
heads and the hidden to be gathered before ``out_proj`` and the
down-projection (``act_out_heads`` / ``act_mlp_hidden`` -> None).  But
``EXACT_TP_RULES`` also shard ``wo`` and ``w_down`` on heads / hidden —
their contracting dims — so the compiler finishes those dots with an
all-reduce: bitwise on the toy configs of tests/test_tp_serving.py at
tp=2, float-close (not bitwise) at phi3 widths.

KV layout: the batched decode state (L, B, capacity, kv_heads*hd) and
every page store shard on the kv-heads dim; block tables, free lists and
refcounts stay replicated HOST state (tp-invariant by construction —
property-tested in tests/test_tp_pool_props.py).

Divisibility: ``tp_size`` must divide ``n_heads`` AND ``n_kv_heads``
(``check_model``).  An indivisible heads dim would trip
``partition_specs``'s head_dim fallback — sharding a contraction dim —
and silently break exactness, so it is rejected instead.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import make_tp_mesh
from ..models.layers import EXACT_TP_RULES
from ..models.sharding import activation_sharding, exact_tp_activation_rules


@dataclasses.dataclass
class TPContext:
    """Mesh + rules + placement helpers for exact-TP serving.

    Shared by every engine the scheduler builds (ONE context per
    scheduler: both engines, both page stores and all host->device
    staging must agree on the mesh, or jit calls would mix arrays
    committed to different device sets and raise)."""

    mesh: jax.sharding.Mesh
    tp_size: int
    axis: str = "model"

    def __post_init__(self):
        self.rules = exact_tp_activation_rules(self.axis)
        self.replicated = NamedSharding(self.mesh, P())

    @classmethod
    def build(cls, tp_size: int, devices=None,
              axis: str = "model") -> "TPContext":
        return cls(make_tp_mesh(tp_size, devices, axis), tp_size, axis)

    # -------------------------------------------------------- validation
    def check_model(self, cfg) -> None:
        for name, val in (("n_heads", cfg.n_heads),
                          ("n_kv_heads", cfg.n_kv_heads)):
            if val % self.tp_size != 0:
                raise ValueError(
                    f"tp_size={self.tp_size} must divide {name}={val} "
                    f"({cfg.name}): the head_dim sharding fallback would "
                    f"split a contraction dim and break the bit-exact TP "
                    f"contract")

    # --------------------------------------------------------- placement
    def shard_params(self, model, params):
        """Commit a param tree onto the mesh under ``EXACT_TP_RULES``."""
        specs = model.partition_specs(rules=EXACT_TP_RULES,
                                      mesh_shape=dict(self.mesh.shape))
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params, specs)

    def shard_state(self, state):
        """Commit a batched DecodeState: K/V (L, B, cap, kv*hd) sharded
        on the kv-heads-major minor dim (whole heads per device, as
        ``check_model`` requires kv % tp == 0), position vector
        replicated."""
        kv = NamedSharding(self.mesh, P(None, None, None, self.axis))
        return dataclasses.replace(
            state,
            k=None if state.k is None else jax.device_put(state.k, kv),
            v=None if state.v is None else jax.device_put(state.v, kv),
            pos=jax.device_put(state.pos, self.replicated))

    def put(self, x, dtype=None) -> jax.Array:
        """Stage a host array as mesh-committed REPLICATED input (a jit
        call must not mix mesh-committed params with default-device
        operands)."""
        return jax.device_put(jnp.asarray(x, dtype), self.replicated)

    def page_sharding(self, ndim: int, kv_axis: int) -> NamedSharding:
        """Sharding for a page array whose kv-heads dim sits at
        ``kv_axis`` (PagedKVStore puts it at 2, PrefixKVStore at 3)."""
        spec: List[Optional[str]] = [None] * ndim
        spec[kv_axis] = self.axis
        return NamedSharding(self.mesh, P(*spec))

    def shard_pages(self, pages: jax.Array, kv_axis: int) -> jax.Array:
        return jax.device_put(pages,
                              self.page_sharding(pages.ndim, kv_axis))

    # ----------------------------------------------------------- context
    @contextlib.contextmanager
    def context(self):
        """The ambient environment every sharded dispatch (and its
        CompileWatch lowering twin) must trace under: the mesh for
        ``with_sharding_constraint``'s bare PartitionSpecs plus the
        exact-TP activation rules."""
        with self.mesh:
            with activation_sharding(self.rules):
                yield

    # ----------------------------------------------------- observability
    def describe(self) -> Dict[str, Any]:
        """The `/status` ``mesh`` section skeleton (the scheduler adds
        per-device memory watermarks from MemoryWatch)."""
        return {
            "axes": {k: int(v) for k, v in self.mesh.shape.items()},
            "tp_size": self.tp_size,
            "devices": [str(d) for d in self.mesh.devices.flat],
        }

"""Tensor-parallel wrappers for the paged attention kernels: shard_map
over the head axis of a 1-D ``("model",)`` mesh.

Attention is embarrassingly parallel over kv heads — the paged kernels
already grid over ``(batch, kv_heads, blocks)`` with no cross-head
reduction — so the TP decomposition is exact by construction: each shard
runs the UNMODIFIED per-device kernel over its contiguous kv-head slice
of the page pool and the matching q-head slice (GQA groups stay whole
because ``tp | n_kv_heads`` and GSPMD shards axes in contiguous chunks),
and the sharded output is literally the head-slice concatenation of the
unsharded output.  No psum, no tolerance: bitwise equality against the
single-device kernel (tests/test_tp_serving.py).

Inputs that stay REPLICATED across the mesh: block tables, per-row
lengths (host-side accounting state — serving/paged_kv.py), and the
span-length vectors.  Only q/k/v/pages are sharded (on their head dim).

Body selection (DESIGN.md §Sharded serving): the shard_map body is the
Pallas kernel, compiled on ``tpu`` and interpreted on every other backend
(``ops.interpret_mode``).  ``use_kernel=False`` swaps in the pure-jnp
reference gather (``kernels.ref``) — an oracle for tests, refused on
``tpu`` so a chip run can never quietly leave the kernel.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax.sharding import PartitionSpec as P

from . import ref
from .ops import interpret_mode
from .paged_append_attention import paged_append_attention
from .paged_decode_attention import paged_decode_attention


def _body(kernel: Callable, reference: Callable,
          use_kernel: bool) -> Callable:
    """The shard_map body: the Pallas kernel (interpreted off ``tpu``),
    or — only when asked for, and never on ``tpu`` — the reference."""
    if use_kernel:
        return functools.partial(kernel, interpret=interpret_mode())
    if not interpret_mode():
        raise ValueError("use_kernel=False: the reference body is a test "
                         "oracle and is refused on tpu")
    return reference


def tp_paged_decode_attention(mesh, q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              lengths: jax.Array, *, axis: str = "model",
                              use_kernel: bool = True) -> jax.Array:
    """Sharded paged flash-decode: q (B, H, hd) and pages (P, K, bs, hd)
    sharded on their head dims over ``axis``; block tables and lengths
    replicated.  Returns (B, H, hd) sharded like q."""
    body = _body(paged_decode_attention, ref.paged_decode_reference,
                 use_kernel)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis, None),                # q heads
                  P(None, axis, None, None),          # k pages kv-heads
                  P(None, axis, None, None),          # v pages kv-heads
                  P(None, None),                      # block tables
                  P(None)),                           # lengths
        out_specs=P(None, axis, None),
        check_vma=False)
    return fn(q, k_pages, v_pages, block_tables, lengths)


def tp_paged_append_attention(mesh, q: jax.Array, k_new: jax.Array,
                              v_new: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_tables: jax.Array,
                              ctx_lens: jax.Array, span_lens: jax.Array,
                              *, axis: str = "model",
                              use_kernel: bool = True) -> jax.Array:
    """Sharded span verification attention: q (B, T, H, hd) and
    k_new/v_new (B, T, K, hd) sharded on their head dims alongside the
    page pool; tables/lengths replicated.  Returns (B, T, H, hd) sharded
    like q.  Same body-selection rule as the decode wrapper."""
    body = _body(paged_append_attention, ref.paged_append_reference,
                 use_kernel)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, axis, None),          # q heads
                  P(None, None, axis, None),          # k_new kv-heads
                  P(None, None, axis, None),          # v_new kv-heads
                  P(None, axis, None, None),          # k pages kv-heads
                  P(None, axis, None, None),          # v pages kv-heads
                  P(None, None),                      # block tables
                  P(None),                            # ctx_lens
                  P(None)),                           # span_lens
        out_specs=P(None, None, axis, None),
        check_vma=False)
    return fn(q, k_new, v_new, k_pages, v_pages, block_tables, ctx_lens,
              span_lens)

"""Mamba2 SSD chunked scan — Pallas TPU kernel.

The SSD (state-space duality) computation of arXiv:2405.21060 splits the
sequence into chunks: a quadratic intra-chunk "attention-like" term (MXU
friendly) plus a linear inter-chunk state recurrence.  This kernel fuses
both for one (batch, head) pair:

  * grid = (batch, heads, n_chunks); the chunk axis is innermost and
    *sequential*, so the running SSM state (P, N) lives in VMEM scratch and
    carries across chunk iterations — the inter-chunk recurrence costs no
    HBM traffic at all.
  * Head-major layouts, so every block's last two dims are TPU tiles
    (multiples of (8, 128) or whole dims): per step x (Q, P), B^T (N, Q),
    C (Q, N), with the GQA-style group->head broadcast resolved in the
    index_map (no repeat in HBM).  Q = chunk length (128 default) keeps
    every matmul MXU-aligned and untransposed: (Q,N)x(N,Q), (Q,Q)x(Q,P),
    (Q,N)x(N,P), (N,Q)x(Q,P) — the running state is held as (N, P).
  * The per-chunk cumulative log-decay cum = cumsum(a*dt) and the decays
    derived from it are computed by the wrapper (small elementwise
    passes): cum arrives as a column (Q, 1) and a row (1, Q), so the
    decay matrix exp(segsum) is a broadcast subtraction — no in-kernel
    cumsum, vector transpose or scalar broadcast.

Emits both the per-position outputs y (B, L, H, P) and the final state
(B, H, P, N) — the latter is what SpecReason snapshots at reasoning-step
boundaries for SSM-family rollback (DESIGN.md §Arch-applicability).

Validated against ``ref.ssd_reference`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, cum_ref, cum_row_ref, tail_ref, decay_ref,
                bt_ref, c_ref, init_ref, y_ref, fin_ref, state_ref, *,
                chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (Q, 1)
    cum = cum_ref[0, 0]                              # (Q, 1) f32
    cum_row = cum_row_ref[0, 0]                      # (1, Q) f32
    bt = bt_ref[0, 0].astype(jnp.float32)            # (N, Q)
    c = c_ref[0, 0].astype(jnp.float32)              # (Q, N)

    xd = x * dt
    # intra-chunk decay matrix L[i,j] = exp(cum_i - cum_j) for j <= i
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(kj <= qi, jnp.exp(cum - cum_row), 0.0)

    scores = jnp.dot(c, bt, preferred_element_type=jnp.float32) * lmat
    y = jnp.dot(scores, xd, preferred_element_type=jnp.float32)

    # contribution of the state entering this chunk
    state = state_ref[...]                            # (N, P)
    y = y + jnp.dot(c * jnp.exp(cum), state,
                    preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state' = state * exp(sum a dt)
    #                        + sum_j exp(cum_end - cum_j) b_j x_j^T
    xb = jnp.dot(bt, xd * tail_ref[0, 0],
                 preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = state * decay_ref[0, 0, 0] + xb

    @pl.when(ic == nc - 1)
    def _emit():
        fin_ref[0, 0] = state_ref[...].astype(fin_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int, init_state: jax.Array,
             interpret: bool = False):
    """x: (B, L, H, P); dt: (B, L, H); a: (H,); b, c: (B, L, G, N);
    init_state: (B, H, P, N).  L must be a multiple of ``chunk``.

    Returns (y (B, L, H, P), final_state (B, H, P, N))."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert l % chunk == 0
    nc = l // chunk
    rep = h // g

    dt_h = dt.transpose(0, 2, 1)                              # (B, H, L)
    adt = a.astype(jnp.float32)[None, :, None] * dt_h.astype(jnp.float32)
    cum = jnp.cumsum(adt.reshape(bsz, h, nc, chunk), axis=-1)
    total = cum[..., -1:]                                     # (B, H, nc, 1)
    tail = jnp.exp(total - cum).reshape(bsz, h, l, 1)
    # per-chunk state decay, lane-broadcast to (1, P) here: Mosaic
    # cannot broadcast a (1, 1) value over sublanes and lanes at once
    decay = jnp.broadcast_to(jnp.exp(total)[..., None], (bsz, h, nc, 1, p))
    cum = cum.reshape(bsz, h, l)

    def col(ib, ih, ic):
        return ib, ih, ic, 0

    grid = (bsz, h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), col),
            pl.BlockSpec((1, 1, chunk, 1), col),
            pl.BlockSpec((1, 1, chunk, 1), col),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, 1), col),
            pl.BlockSpec((1, 1, 1, 1, p),
                         lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec((1, 1, n, chunk),
                         lambda ib, ih, ic, r=rep: (ib, ih // r, 0, ic)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic, r=rep: (ib, ih // r, ic, 0)),
            pl.BlockSpec((1, 1, n, p), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), col),
            pl.BlockSpec((1, 1, n, p), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt_h[..., None], cum[..., None],
      cum[:, :, None, :], tail, decay, b.transpose(0, 2, 3, 1),
      c.transpose(0, 2, 1, 3),
      init_state.astype(jnp.float32).transpose(0, 1, 3, 2))
    return y.transpose(0, 2, 1, 3), fin.transpose(0, 1, 3, 2)

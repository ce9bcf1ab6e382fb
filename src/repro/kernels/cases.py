"""Every Pallas kernel of this package called at published widths in bf16.

The attention kernels take phi3-mini-3.8b's attention geometry (32 query
and 32 kv heads of head_dim 96) with serving shapes: a batch of 8 rows,
1024-token contexts, 16-token pages in a 4096-page pool, and a verify
span of gamma + 1 = 5 tokens padded to 8.  ``ssd_scan`` takes
mamba2-1.3b's (64 SSD heads of head_dim 64, state 128, one B/C group,
chunk 128).

``chip_smoke.py`` runs these cases compiled on the chip against
``kernels/ref.py``; ``tests/test_chip_compile.py`` compiles them for a
described v5e.  Arguments come from ``make_args(key)``, so
``jax.eval_shape(case.make_args, key)`` gives their shapes without
allocating.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.mamba2_1_3b import CONFIG as MAMBA2
from ..configs.phi3_mini_3_8b import CONFIG as PHI3
from . import ref
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .paged_append_attention import paged_append_attention
from .paged_decode_attention import paged_decode_attention
from .ssd_scan import ssd_scan

DTYPE = jnp.bfloat16
BATCH = 8
CONTEXT = 1024          # decode context per row
PREFILL = 256           # flash-attention sequence
BLOCK = 16              # tokens per KV page (serving/kv_manager.py)
PAGES = 4096
SPAN = 8                # gamma + 1 = 5, padded to a sublane multiple
SSD_BATCH, SSD_LEN = 2, 512

# bf16 carries 8 significant bits: the kernels round their output to bf16
# (2^-9 relative) and feed bf16 probabilities to the P@V matmul, while the
# reference computes in float32 at highest precision.  2e-2 of the output's
# scale is about 5x that rounding.
BF16_ATTN_TOL = 2e-2
BF16_ATTN_WHY = ("bf16 inputs and output: 2^-9 output rounding plus bf16 "
                 "probabilities in P@V, against a float32 reference")
# ssd_scan carries its state in float32 but reads bf16 x/dt/B/C and rounds
# y to bf16; the chunked form also reorders the recurrence's sums.
BF16_SSD_TOL = 2e-2
BF16_SSD_WHY = ("bf16 x/dt/B/C and output, chunked vs sequential order of "
                "the float32 state sums")


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    kernel: Callable            # jitted; takes ``interpret`` as a keyword
    reference: Callable
    make_args: Callable[[jax.Array], Tuple[jax.Array, ...]]
    tol: float                  # on max|kernel - ref| / max(1, max|ref|)
    why: str
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(DTYPE)


def _pages(key):
    kk, kv = jax.random.split(key)
    shape = (PAGES, PHI3.n_kv_heads, BLOCK, PHI3.head_dim)
    return _normal(kk, shape), _normal(kv, shape)


def _tables(key):
    """Each row owns CONTEXT // BLOCK distinct pages of the pool."""
    nb = CONTEXT // BLOCK
    perm = jax.random.permutation(key, PAGES)[:BATCH * nb]
    return perm.reshape(BATCH, nb).astype(jnp.int32)


def _flash_args(key):
    kq, kk, kv = jax.random.split(key, 3)
    h, kh, hd = PHI3.n_heads, PHI3.n_kv_heads, PHI3.head_dim
    return (_normal(kq, (BATCH, h, PREFILL, hd)),
            _normal(kk, (BATCH, kh, PREFILL, hd)),
            _normal(kv, (BATCH, kh, PREFILL, hd)))


def _decode_args(key):
    kq, kk, kv, kl = jax.random.split(key, 4)
    h, kh, hd = PHI3.n_heads, PHI3.n_kv_heads, PHI3.head_dim
    return (_normal(kq, (BATCH, h, hd)),
            _normal(kk, (BATCH, kh, CONTEXT, hd)),
            _normal(kv, (BATCH, kh, CONTEXT, hd)),
            jax.random.randint(kl, (BATCH,), 1, CONTEXT + 1, jnp.int32))


def _paged_decode_args(key):
    kq, kp, kt, kl = jax.random.split(key, 4)
    k_pages, v_pages = _pages(kp)
    return (_normal(kq, (BATCH, PHI3.n_heads, PHI3.head_dim)),
            k_pages, v_pages, _tables(kt),
            jax.random.randint(kl, (BATCH,), 1, CONTEXT + 1, jnp.int32))


def _paged_append_args(key):
    kq, kk, kv, kp, kt, kl = jax.random.split(key, 6)
    h, kh, hd = PHI3.n_heads, PHI3.n_kv_heads, PHI3.head_dim
    k_pages, v_pages = _pages(kp)
    # full spans: the kernel leaves rows past span_len undefined
    return (_normal(kq, (BATCH, SPAN, h, hd)),
            _normal(kk, (BATCH, SPAN, kh, hd)),
            _normal(kv, (BATCH, SPAN, kh, hd)),
            k_pages, v_pages, _tables(kt),
            jax.random.randint(kl, (BATCH,), 0, CONTEXT + 1, jnp.int32),
            jnp.full((BATCH,), SPAN, jnp.int32))


def _ssd_args(key):
    kx, kd, ka, kb, kc = jax.random.split(key, 5)
    h, p = MAMBA2.ssm_n_heads, MAMBA2.ssm_head_dim
    g, n = MAMBA2.ssm_n_groups, MAMBA2.ssm_state
    shape = (SSD_BATCH, SSD_LEN)
    # dt = softplus(.) around mamba2's init range, A = -exp(A_log) in [-16, -1]
    dt = jax.nn.softplus(jax.random.normal(kd, shape + (h,)) - 3.0)
    a = -jax.random.uniform(ka, (h,), jnp.float32, 1.0, 16.0)
    return (_normal(kx, shape + (h, p)), dt.astype(DTYPE), a,
            _normal(kb, shape + (g, n)), _normal(kc, shape + (g, n)),
            jnp.zeros((SSD_BATCH, h, p, n), jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_kernel(x, dt, a, b, c, init, interpret=False):
    return ssd_scan(x, dt, a, b, c, MAMBA2.ssm_chunk, init,
                    interpret=interpret)


ATTENTION_CASES = (
    KernelCase("flash_attention", flash_attention, ref.mha_reference,
               _flash_args, BF16_ATTN_TOL, BF16_ATTN_WHY,
               kwargs={"causal": True}),
    KernelCase("decode_attention", decode_attention, ref.decode_reference,
               _decode_args, BF16_ATTN_TOL, BF16_ATTN_WHY),
    KernelCase("paged_decode_attention", paged_decode_attention,
               ref.paged_decode_reference, _paged_decode_args,
               BF16_ATTN_TOL, BF16_ATTN_WHY),
    KernelCase("paged_append_attention", paged_append_attention,
               ref.paged_append_reference, _paged_append_args,
               BF16_ATTN_TOL, BF16_ATTN_WHY),
)
SSD_CASE = KernelCase("ssd_scan", _ssd_kernel, ref.ssd_reference, _ssd_args,
                      BF16_SSD_TOL, BF16_SSD_WHY)
ALL_CASES = ATTENTION_CASES + (SSD_CASE,)

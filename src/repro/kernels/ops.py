"""Jit'd public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic; on every other backend
they run in ``interpret=True`` mode (the kernel body executes in Python,
validating the exact TPU dataflow).  The backend alone decides.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention
from .flash_attention import flash_attention
from .ssd_scan import ssd_scan


def interpret_mode(backend: Optional[str] = None) -> bool:
    """Whether Pallas kernels run interpreted: everywhere but ``tpu``."""
    return (backend or jax.default_backend()) != "tpu"


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True) -> jax.Array:
    """(B,H,S,hd) x (B,K,S,hd)^2 -> (B,H,S,hd)."""
    return flash_attention(q, k, v, causal=causal, interpret=interpret_mode())


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array) -> jax.Array:
    """(B,H,hd) x (B,K,S,hd)^2 + lengths (B,) -> (B,H,hd)."""
    return decode_attention(q, k_cache, v_cache, lengths,
                            interpret=interpret_mode())


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, chunk: int,
        init_state: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan; see ssd_scan.py.  Returns (y, final_state)."""
    if init_state is None:
        bsz, _, h, p = x.shape
        n = b.shape[-1]
        init_state = jnp.zeros((bsz, h, p, n), jnp.float32)
    return ssd_scan(x, dt, a, b, c, chunk, init_state,
                    interpret=interpret_mode())

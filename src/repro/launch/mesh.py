"""Production mesh construction + logical->mesh sharding rule sets.

TPU v5e target: 256 chips per pod (16x16), optionally 2 pods = 512 chips.
Constructed as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import)."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax

from ..models.layers import DEFAULT_RULES, FSDP_RULES
from ..models.sharding import default_activation_rules

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link


def auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: sharding is propagated
    by GSPMD from the ``constrain`` hints (models/sharding.py).  Under
    the ``Explicit`` default those hints are refused and a dot whose
    contracting dim is sharded raises instead of gathering."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for CPU distribution tests (8 forced host devices)."""
    return auto_mesh(shape, axes)


def make_tp_mesh(tp_size: int, devices=None, axis: str = "model"):
    """1-D ``(axis,)`` mesh over the first ``tp_size`` devices — the
    serving stack's tensor-parallel mesh (``serving/tp.py`` builds its
    TPContext on it; the same ``model`` axis name the param/activation
    rule sets already target), its axis ``Auto`` (``auto_mesh``): the
    exact-TP all-gathers are GSPMD's answer to the ``constrain`` hints."""
    if tp_size < 1:
        raise ValueError(f"tp_size must be >= 1, got {tp_size}")
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < tp_size:
        raise ValueError(
            f"tp_size={tp_size} needs {tp_size} devices, "
            f"have {len(devices)}")
    return auto_mesh((tp_size,), (axis,), devices=devices[:tp_size])


def param_rules(mode: str = "tp") -> Dict[str, Any]:
    """Parameter sharding rule set.

    "tp": baseline tensor parallelism (paper-faithful: params replicated
          across data, sharded over model — vLLM TP analog).
    "fsdp": additionally shard the d_model dim over data (ZeRO-3-like) —
          beyond-paper memory optimization for train_4k."""
    if mode == "fsdp":
        return dict(FSDP_RULES)
    return dict(DEFAULT_RULES)


def activation_rules(mesh, *, shard_batch: bool = True) -> Dict[str, Any]:
    data_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return default_activation_rules(data_axes=data_axes,
                                    shard_batch=shard_batch)


def batch_axes(mesh, global_batch: int) -> Optional[Tuple[str, ...]]:
    """Mesh axes to shard the batch dim over (None if batch too small)."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if global_batch % n == 0 and global_batch >= n:
        return tuple(axes)
    return None

"""Distribution tests: divisibility-aware partition specs, and an
end-to-end 8-device CPU pjit run whose sharded forward matches the
single-device forward.  The forced device count comes from
tests/conftest.py (set before backend init, restored at session end);
the pjit run stays in a subprocess only to keep its XLA compilations
out of this process's caches."""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec, partition_specs
from repro.models.model import Model
from repro.configs.registry import ARCHS


def test_divisible_dims_shard():
    spec = {"w": ParamSpec((64, 1024), ("embed", "mlp"))}
    ps = partition_specs(spec, mesh_shape={"data": 2, "model": 16})
    assert ps["w"] == P(None, "model")


def test_indivisible_dim_replicates():
    spec = {"w": ParamSpec((64, 100), ("embed", "mlp"))}
    ps = partition_specs(spec, mesh_shape={"data": 2, "model": 16})
    assert ps["w"] == P(None, None)


def test_kv_heads_fallback_to_head_dim():
    """GQA kv=8 on a 16-way model axis -> head_dim carries the sharding."""
    spec = {"wk": ParamSpec((512, 8, 64), ("embed", "kv_heads", "head_dim"))}
    ps = partition_specs(spec, mesh_shape={"model": 16})
    assert ps["wk"] == P(None, None, "model")


def test_heads_preferred_when_divisible():
    spec = {"wq": ParamSpec((512, 32, 64), ("embed", "heads", "head_dim"))}
    ps = partition_specs(spec, mesh_shape={"model": 16})
    assert ps["wq"] == P(None, "model", None)


def test_no_mesh_axis_used_twice():
    spec = {"w": ParamSpec((32, 64), ("heads", "kv_heads"))}
    ps = partition_specs(spec, mesh_shape={"model": 16})
    used = [a for a in ps["w"] if a is not None]
    assert len(used) == len(set(used))


@pytest.mark.parametrize("arch", ["yi-34b", "hymba-1.5b", "whisper-base",
                                  "qwen3-moe-235b-a22b"])
def test_full_arch_specs_all_divisible(arch):
    """Every generated PartitionSpec must divide its dim on the 16x16
    mesh (pjit rejects uneven input shardings)."""
    cfg = ARCHS[arch]
    model = Model(cfg)
    mesh_shape = {"data": 16, "model": 16}
    specs = model.partition_specs(mesh_shape=mesh_shape)
    params = model.spec()
    import jax
    from repro.models.layers import is_spec

    flat_p = jax.tree.leaves(params, is_leaf=is_spec)
    flat_s = jax.tree.leaves(specs,
                             is_leaf=lambda x: isinstance(x, P))
    for pspec, sspec in zip(flat_p, flat_s):
        for dim, ax in zip(pspec.shape, tuple(sspec)):
            if ax is None:
                continue
            size = mesh_shape[ax] if isinstance(ax, str) else \
                int(jax.numpy.prod(jax.numpy.asarray(
                    [mesh_shape[a] for a in ax])))
            assert dim % size == 0, (arch, pspec.shape, tuple(sspec))


SUBPROCESS_PROG = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.config import ModelConfig
    from repro.models.model import Model
    from repro.launch.mesh import make_test_mesh
    from repro.models.sharding import activation_sharding, \\
        default_activation_rules

    cfg = ModelConfig(name="x", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=64, n_experts=4, top_k=2,
                      moe_group_size=16).validate()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)

    ref, _ = model.forward(params, toks)   # single-logical-device

    mesh = make_test_mesh((2, 4))
    pspecs = model.partition_specs(mesh_shape=dict(mesh.shape))
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    rules = default_activation_rules(("data",))
    with mesh:
        with activation_sharding(rules):
            f = jax.jit(lambda p, t: model.forward(p, t)[0],
                        in_shardings=(psh, NamedSharding(mesh,
                                                         P("data", None))))
            out = f(params, toks)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 2e-3, f"sharded forward mismatch: {err}"
    print("SHARDED_OK", err)
""")


def test_sharded_forward_matches_single_device(forced_xla_env):
    # forced device count comes from the conftest fixture's env (save/
    # restore handled there) — no raw os.environ mutation in the child
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG],
                       capture_output=True, text=True, timeout=600,
                       env=forced_xla_env,
                       cwd=Path(__file__).resolve().parents[1])
    assert "SHARDED_OK" in r.stdout, r.stdout + r.stderr


def test_constrain_raises_on_unappliable_spec():
    """Inside a rules context ``constrain`` applies the constraint or
    raises: a rule naming an axis the mesh does not have is an error, not
    an unconstrained array.  Outside any context it is the identity."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_tp_mesh
    from repro.models.sharding import (activation_sharding, constrain,
                                       default_activation_rules)

    x = jnp.ones((4, 8))
    assert constrain(x, ("act_batch", None)) is x
    rules = default_activation_rules(data_axes=("data",))
    with make_tp_mesh(2), activation_sharding(rules):
        with pytest.raises(ValueError, match="data"):
            jax.jit(lambda a: constrain(a, ("act_batch", None)))(x)

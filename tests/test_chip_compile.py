"""Compile the Pallas kernels at published widths for a described TPU v5e.

No chip is attached: the TPU compiler that ships with JAX compiles for a
v5e:2x2 topology it is only told about, and refuses what the chip would
refuse (unaligned tiles, too much VMEM).  Each compiled program must hold
the Mosaic kernel (``tpu_custom_call``), i.e. the kernel really lowered
to the chip and was not interpreted.  The cases are the ones
``chip_smoke.py`` runs on the chip (``repro.kernels.cases``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles (a
program compiled for a described chip cannot be read back without one).
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import ALL_CASES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", ALL_CASES, ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    shapes = jax.eval_shape(case.make_args, jax.random.PRNGKey(0))
    shapes = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
              for s in shapes]
    compiled = case.kernel.lower(*shapes, **case.kwargs,
                                 interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()

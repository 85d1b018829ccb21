"""Compile the main path's Pallas kernel for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip
that is described, not attached, and refuses what Mosaic would refuse
on the chip (block layouts, VMEM, unsupported primitives).  The
topology is described inside a fixture, so importing this file never
loads the TPU library, and every test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.apoz import apoz_counts_pallas, column_block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep the cache out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", [(2048, 256), (2048, 512), (2048, 64)])
def test_apoz_kernel_compiles_for_v5e(one_chip, shape):
    """The scorer's batch (2048) at the paper's hidden widths (256, 64)
    and a wider layer (512, several column blocks)."""
    n = shape[1]
    acts = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = apoz_counts_pallas.lower(acts, bn=column_block(n),
                                        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()

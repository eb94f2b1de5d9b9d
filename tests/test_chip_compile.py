"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: each case lowers and compiles for a described (not attached)
``v5e:2x2`` chip and checks that the kernel is in the compiled program as a
``tpu_custom_call``.  This is what interpret mode cannot show: block shapes
the TPU's (8, 128) tiling refuses, and VMEM use beyond the scoped limit.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunk_pack.chunk_pack import pack_chunks_kernel
from repro.kernels.chunk_router.chunk_router import (dest_histogram2d_kernel,
                                                     dest_histogram_kernel)

#: chip_smoke.py's chunk: 512 KiB of int32 words
CHUNK_WORDS = 131072


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    """Compile ``fn`` at ``shapes`` with the persistent cache off (an
    entry compiled for an absent chip could not be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


# the bare chunk, and the engine's fused send row (path hash, chunk id,
# chunk, occupancy column): a width that is not a multiple of 128 lanes
@pytest.mark.parametrize("width", [CHUNK_WORDS, CHUNK_WORDS + 3])
def test_chunk_pack_compiles_at_chunk_width(one_chip, width):
    payload = jax.ShapeDtypeStruct((512, width), jnp.int32,
                                   sharding=one_chip)
    idx = jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip)
    text = _compile_text(
        lambda p, i: pack_chunks_kernel(p, i, interpret=False), payload, idx)
    assert "tpu_custom_call" in text


def test_dest_histogram2d_compiles(one_chip):
    dest = jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=one_chip)
    text = _compile_text(
        lambda d: dest_histogram2d_kernel(d, n_bins=8, interpret=False), dest)
    assert "tpu_custom_call" in text


def test_dest_histogram_compiles(one_chip):
    dest = jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip)
    text = _compile_text(
        lambda d: dest_histogram_kernel(d, n_bins=8, interpret=False), dest)
    assert "tpu_custom_call" in text

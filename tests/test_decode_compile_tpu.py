"""The Pallas decode kernel compiles for a TPU v5e at the served sizes.

Each case lowers ``_decode_fused(..., use_pallas=True)`` — the jitted op
``codec/batch.py`` dispatches, with the column block the served code picks
(``block_columns``), on a stream alone and as served, with the copy back
in canvas order — for one chip of a described ``v5e:2x2`` topology, and
compiles it with the TPU compiler.  Nothing runs: these guard what the
chip's compiler would refuse (VMEM overflow above all: a fixed 128-column
block does not fit once a GOP is 16 frames deep).  F covers a keyframe
alone, GOP 16 and GOP 30 (bucketed to 32); M = 32,768 columns is one 1080p
frame's 32,400 8x8 blocks, bucketed.

The topology is described inside a fixture, so only the worker that runs
these tests loads the TPU compiler; where it cannot be described, they skip.
"""
import os

import pytest

F_DEPTHS = [1, 16, 32]
M_COLUMNS = [64, 32768]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("m", M_COLUMNS)
@pytest.mark.parametrize("f", F_DEPTHS)
def test_decode_kernel_compiles_for_v5e(one_chip, f, m):
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode.decode import (SLAB_VMEM_BYTES,
                                             VMEM_BUDGET_BYTES,
                                             block_columns)
    from repro.kernels.decode.ops import _decode_fused

    blk = block_columns(f, m)
    assert m % blk == 0
    assert f * blk * SLAB_VMEM_BYTES <= VMEM_BUDGET_BYTES
    q = jax.ShapeDtypeStruct((f, m, 8, 8), jnp.int16, sharding=one_chip)
    compiled = _decode_fused.lower(q, qp=8, use_pallas=True,
                                   interpret=False).compile()
    # the Mosaic kernel itself is in the program, not the jnp reference
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("f,m", [(1, 64), (32, 32768)])
def test_decode_program_with_copy_back_compiles_for_v5e(one_chip, f, m):
    """The program a served dispatch runs: the flat stream and the column
    table in, the kernel, and the copy back in canvas order."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.decode.ops import _decode_fused

    q = jax.ShapeDtypeStruct((f * m * 64,), jnp.int16, sharding=one_chip)
    tab = jax.ShapeDtypeStruct((m,), jnp.uint32, sharding=one_chip)
    compiled = _decode_fused.lower(q, tab, qp=8, use_pallas=True,
                                   interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == f * m * 64 * 4

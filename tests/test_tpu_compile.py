"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned slices, blocks that break the (8, 128) tiling rule), which
interpret mode cannot show.  Each test checks that the kernel survived
as a Mosaic custom call.  The topology is described inside a fixture so
that only the worker running this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.embedding_reduce import kernel as er_kernel
from repro.kernels.stream_copy import kernel as sc_kernel


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes, **static):
    return jax.jit(lambda *a: fn(*a, **static)).lower(*shapes).compile().as_text()


def test_embedding_reduce_compiles_at_dlrm_width(one_chip):
    V, D, B, K = 1 << 20, 128, 256, 16
    text = _hlo(er_kernel.embedding_reduce,
                jax.ShapeDtypeStruct((V, D), jnp.float32, sharding=one_chip),
                jax.ShapeDtypeStruct((B, K), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((B, K), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,cols,src,out", [
    (4096, 2048, jnp.bfloat16, jnp.bfloat16),  # aligned chunks
    (4100, 2048, jnp.bfloat16, jnp.bfloat16),  # ragged tail of 4 rows
    (100, 512, jnp.float32, jnp.float32),  # fewer rows than one chunk
    (1000, 384, jnp.bfloat16, jnp.float32),  # ragged, fused bf16 -> f32
])
def test_stream_copy_compiles(one_chip, rows, cols, src, out):
    text = _hlo(sc_kernel.stream_copy,
                jax.ShapeDtypeStruct((rows, cols), src, sharding=one_chip),
                block_rows=256, out_dtype=out)
    assert "tpu_custom_call" in text

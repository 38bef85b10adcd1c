"""Pallas TPU embedding-bag reduction kernel.

The paper's DLRM embedding-reduction workload (§5.2) is a random-row
gather + weighted sum over a large table.  TPU adaptation: the bag
indices and weights are **scalar-prefetched** into SMEM, and each grid
step owns a block of ``BAGS`` bags.  It starts one single-row DMA per
lookup straight from the table in HBM into a VMEM staging buffer (the
cache-bypass streaming access the paper recommends: no reuse, no
pollution), waits for them, and sums the staged rows into an
``(BAGS, D)`` output tile in float32.

Rows move by manual DMA rather than through a ``BlockSpec``: a
``(1, D)`` block breaks the TPU's (8, 128) tiling rule, and the chip's
compiler refuses it, while a one-row DMA is legal.  Bags are grouped by
``BAGS`` so the output tile is tiling-aligned too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bags per grid step: one sublane tile of output rows.
BAGS = 8
#: lookups per ``pallas_call``: indices and weights live in SMEM, which
#: is small, so larger batches run as several calls.
MAX_LOOKUPS = 8192


def _kernel(idx_ref, w_ref, table_ref, out_ref, rows, sem, *, bag):
    n = BAGS * bag
    base = pl.program_id(0) * n

    def row_copy(j):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(idx_ref[base + j], 1)],
            rows.at[pl.ds(j, 1)], sem)

    def start(j, carry):
        row_copy(j).start()
        return carry

    def wait(j, carry):
        row_copy(j).wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    d = out_ref.shape[1]
    for b in range(BAGS):
        def add(k, acc, b=b):
            j = b * bag + k
            row = rows[pl.ds(j, 1), :].astype(jnp.float32)
            return acc + row * w_ref[base + j]

        out_ref[pl.ds(b, 1), :] = jax.lax.fori_loop(
            0, bag, add, jnp.zeros((1, d), jnp.float32))


def _reduce_chunk(table, idx, w, interpret):
    """One ``pallas_call`` over ``idx.shape[0]`` bags (a multiple of BAGS)."""
    B, K = idx.shape
    D = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // BAGS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((BAGS, D), lambda i, idx, w: (i, 0)),
        scratch_shapes=[pltpu.VMEM((BAGS * K, D), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, bag=K),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        interpret=interpret,
    )
    return fn(idx.reshape(-1), w.reshape(-1), table)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_reduce(
    table: jax.Array,  # (V, D)
    indices: jax.Array,  # (B, K) int32
    weights: jax.Array,  # (B, K)
    *,
    interpret: bool = False,
) -> jax.Array:
    if not interpret and jnp.dtype(table.dtype).itemsize != 4:
        # Staged rows are read one sublane at a time, which the chip's
        # compiler allows for 32-bit data only.
        raise ValueError(f"embedding_reduce on TPU needs a 32-bit table, "
                         f"got {table.dtype}")
    B, K = indices.shape
    pad = -B % BAGS
    idx = jnp.pad(indices.astype(jnp.int32), ((0, pad), (0, 0)))
    w = jnp.pad(weights.astype(jnp.float32), ((0, pad), (0, 0)))
    step = max(BAGS, MAX_LOOKUPS // max(K, 1) // BAGS * BAGS)
    outs = [_reduce_chunk(table, idx[s:s + step], w[s:s + step], interpret)
            for s in range(0, B + pad, step)]
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out[:B].astype(table.dtype)

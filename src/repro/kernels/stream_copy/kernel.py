"""Pallas TPU double-buffered streaming (cache-bypass) migration kernel.

The nt-store / movdir64B analogue from the paper's §6 guidelines, now a
real migration pipeline instead of a blockwise memcpy: page runs move
HBM -> VMEM staging -> HBM through explicitly double-buffered async
DMAs, so chunk i's copy-out overlaps chunk i+1's copy-in and the whole
transfer overlaps surrounding compute instead of serializing on it.
Nothing is reused after the single pass, so no cache-like resource is
polluted; the VMEM chunk is the explicit analogue of the 64 B
cache-bypass granule.  The optional dtype cast (compressed-staging
bf16 <-> fp32 moment pages) happens in VMEM between the in- and
out-DMAs — still a single pass over the data.

Pipeline structure (slots 0/1 double-buffer the full chunks; a ragged
tail gets dedicated slot 2 whose in-DMA starts up front so it rides
under the whole full-chunk pipeline):

    in-DMA(ci+1) ║ wait-in(ci) → cast in VMEM → out-DMA(ci) ║ wait-out(ci-2)

Every DMA moves a whole number of sublane tiles (8 rows of 32-bit data,
16 of 16-bit, 32 of 8-bit) from a tile-aligned row, because the chip's
compiler refuses any other slice.  The kernel therefore copies the
tile-aligned prefix of the rows, and the last ``N % tile`` rows (fewer
than one tile) are written into its output by XLA.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM budget for the six staging buffers (3 in + 3 out slots).
VMEM_BUDGET = 8 << 20


def _row_tile(*dtypes) -> int:
    """Rows per sublane tile for the narrowest dtype involved."""
    return max(8 * (4 // np.dtype(d).itemsize) for d in dtypes)


def _plan(n_rows: int, n_cols: int, block_rows: int, src_dtype, out_dtype
         ) -> tuple[int, int, int]:
    """``(block_rows, n_full, tail_rows)`` for the kernel's share of an
    ``(n_rows, n_cols)`` copy: ``n_full`` chunks of ``block_rows`` rows,
    then ``tail_rows`` more.  Both row counts are whole tiles, and the
    rows covered are the tile-aligned prefix of the array."""
    tile = _row_tile(src_dtype, out_dtype)
    aligned = n_rows // tile * tile
    if aligned == 0:
        return 0, 0, 0
    row_bytes = n_cols * (np.dtype(src_dtype).itemsize
                          + np.dtype(out_dtype).itemsize)
    fit = VMEM_BUDGET // (3 * row_bytes)
    block_rows = max(tile, min(block_rows, aligned, fit) // tile * tile)
    n_full, tail = divmod(aligned, block_rows)
    return block_rows, n_full, tail


def _migrate_kernel(src_ref, out_ref, *, n_full, tail_rows, block_rows):
    """Single-program kernel; the chunk loop plays the grid role so the
    double-buffered DMA chain is explicit rather than compiler-implied."""
    tail_start = n_full * block_rows

    def body(ins, outs, in_sems, out_sems):
        def in_dma(slot, start, rows):
            return pltpu.make_async_copy(
                src_ref.at[pl.ds(start, rows)],
                ins.at[slot, pl.ds(0, rows)],
                in_sems.at[slot])

        def out_dma(slot, start, rows):
            return pltpu.make_async_copy(
                outs.at[slot, pl.ds(0, rows)],
                out_ref.at[pl.ds(start, rows)],
                out_sems.at[slot])

        if tail_rows:
            # tail in-DMA issued first: overlaps the full-chunk pipeline.
            in_dma(2, tail_start, tail_rows).start()

        in_dma(0, 0, block_rows).start()

        def step(ci, carry):
            cur = jax.lax.rem(ci, 2)
            nxt = jax.lax.rem(ci + 1, 2)

            @pl.when(ci + 1 < n_full)
            def _prefetch():
                in_dma(nxt, (ci + 1) * block_rows, block_rows).start()

            in_dma(cur, ci * block_rows, block_rows).wait()

            @pl.when(ci >= 2)
            def _drain_prev():
                # outs[cur] still ships chunk ci-2; reclaim it.
                out_dma(cur, (ci - 2) * block_rows, block_rows).wait()

            outs[cur, ...] = ins[cur, ...].astype(out_ref.dtype)
            out_dma(cur, ci * block_rows, block_rows).start()
            return carry

        jax.lax.fori_loop(0, n_full, step, 0)

        # Drain the last (up to) two in-flight out-DMAs.
        for ci in range(max(0, n_full - 2), n_full):
            out_dma(ci % 2, ci * block_rows, block_rows).wait()

        if tail_rows:
            in_dma(2, tail_start, tail_rows).wait()
            outs[2, pl.ds(0, tail_rows)] = (
                ins[2, pl.ds(0, tail_rows)].astype(out_ref.dtype))
            out_dma(2, tail_start, tail_rows).start()
            out_dma(2, tail_start, tail_rows).wait()

    M = src_ref.shape[1]
    pl.run_scoped(
        body,
        ins=pltpu.VMEM((3, block_rows, M), src_ref.dtype),
        outs=pltpu.VMEM((3, block_rows, M), out_ref.dtype),
        in_sems=pltpu.SemaphoreType.DMA((3,)),
        out_sems=pltpu.SemaphoreType.DMA((3,)),
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "out_dtype", "interpret"))
def stream_copy(
    src: jax.Array,  # (N, M) — page-major layout
    *,
    block_rows: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    out_dtype = out_dtype or src.dtype
    N, M = src.shape
    block_rows, n_full, tail_rows = _plan(N, M, block_rows, src.dtype,
                                          out_dtype)
    head = n_full * block_rows + tail_rows
    if head == 0:
        return src.astype(out_dtype)
    fn = pl.pallas_call(
        functools.partial(_migrate_kernel, n_full=n_full,
                          tail_rows=tail_rows, block_rows=block_rows),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((N, M), out_dtype),
        interpret=interpret,
    )
    out = fn(src)
    if head < N:
        out = out.at[head:].set(src[head:].astype(out_dtype))
    return out

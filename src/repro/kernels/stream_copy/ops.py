"""Jit'd public wrapper for the streaming migration kernel.

Arbitrary 2-D row counts go through the kernel: it copies the
tile-aligned rows, and XLA writes the last few (fewer than one sublane
tile).  Rows narrower than one 128-lane vector are copied as a
lane-dense view of the same bytes (a copy is layout-blind); payloads
with no such view, non-2-D payloads and empty arrays use the reference
path.
"""
from __future__ import annotations

import jax

from repro.kernels.common import interpret_default
from repro.kernels.stream_copy import kernel, ref

#: lanes of one TPU vector register; the kernel's rows must fill them.
LANES = 128


def stream_copy(src: jax.Array, *, out_dtype=None, block_rows: int = 256,
                use_kernel: bool = True) -> jax.Array:
    if not use_kernel or src.ndim != 2 or src.size == 0:
        return ref.stream_copy(src, out_dtype)
    view = src
    if src.shape[1] % LANES:
        if src.size % LANES:
            return ref.stream_copy(src, out_dtype)
        view = src.reshape(-1, LANES)
    out = kernel.stream_copy(
        view, block_rows=block_rows, out_dtype=out_dtype,
        interpret=interpret_default(),
    )
    return out.reshape(src.shape)

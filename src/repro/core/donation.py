"""Donation-based in-place buffer updates for tier actuation.

The last non-O(Δ) cost in the probe-epoch loop (ISSUE 7): a stable-path
repartition plans, ships, and re-indexes in O(Δ), but materializing the
functional update still paid one full copy-on-write of every RECEIVING
shard, because immutable jax buffers cannot be patched in place.  When
the caller provably drops the parent tensor — the Caption actuation
pattern ``it = it.repartition_weights(...)`` — that copy is pure waste:
XLA buffer *donation* lets the scatter reuse the input buffer, so the
update writes only the moved rows.

``donated_update`` is that path: a jitted ``donate_argnums=(0,)``
scatter shared by ``InterleavedTensor._scatter_bucketed``, the
stable-path ``repartition``, and ``TieredKVCache._retile``.  On this
CPU backend donation is real — the output aliases the
input buffer (asserted by tests/test_actuation.py via
``unsafe_buffer_pointer``) — and on TPU/GPU it is the standard aliasing
path.  Index arrays are padded to power-of-two buckets (out-of-range
rows, dropped by the scatter) so a Caption walk's varying delta sizes
hit a bounded number of jit traces.

DONATION CONTRACT: passing ``donate=True`` anywhere upstream asserts
that the parent object — and any ancestor sharing the receiving
buffers — is dead after the call.  The parent's arrays are deleted
(accessing them raises).

VIEW HAZARD: a live zero-copy host view (``np.asarray(buf)``) pins an
external reference on the buffer, which blocks XLA input/output
aliasing — the "donated" call then silently materializes a full copy
(correct, but the O(Δ) win is gone).  Every donated call site must
drop its host mirrors / staged views of the receiving buffer first and
re-view the returned array; staging data must be gathered as copies
(fancy indexing), never as views.

``FULL_SHARD_COPIES`` counts every full receiving-shard copy the
non-donated paths still perform; benchmarks assert the donated stable
path leaves it at zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compute_on import compute_on


class CopyCounter:
    """Counts full receiving-shard materializations (bench/test probe)."""

    __slots__ = ("count",)

    def __init__(self) -> None:
        self.count = 0

    def bump(self, n: int = 1) -> None:
        self.count += n

    def reset(self) -> int:
        out, self.count = self.count, 0
        return out


#: full copy-on-write shard materializations since last reset — the
#: quantity the donated path eliminates on the stable path.
FULL_SHARD_COPIES = CopyCounter()


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pad_to_bucket(rows: np.ndarray, values, n_rows: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Pad (rows, values) to the next power-of-two length.

    Pad rows point at ``n_rows`` (one past the end) and are dropped by
    the scatter's ``mode="drop"``; pad values are zeros.  Bounded bucket
    count = bounded jit traces across a walk of varying delta sizes."""
    rows = np.asarray(rows, np.int64)
    values = np.asarray(values)
    k = rows.shape[0]
    cap = _next_pow2(k)
    if cap == k:
        return rows, values
    rows_p = np.full((cap,), n_rows, np.int64)
    rows_p[:k] = rows
    vals_p = np.zeros((cap,) + values.shape[1:], values.dtype)
    vals_p[:k] = values
    return rows_p, vals_p


def _row_update(part, rows, values, op: str):
    ref = part.at[rows]
    if op == "set":
        return ref.set(values, mode="drop")
    return ref.add(values, mode="drop")


_donated_row_update = jax.jit(_row_update, donate_argnums=(0,),
                              static_argnames=("op",))


@functools.lru_cache(maxsize=64)
def _donated_row_update_sharded(op: str, sharding):
    # memory_kind backends must keep the output in the donated input's
    # memory space — out_shardings pins it (a bare jit could migrate the
    # result back to default device memory, silently un-tiering the shard).
    # XLA cannot scatter into pinned_host memory from the device, so a
    # pinned_host shard is patched on the host: only rows and values
    # cross the link, and the donated buffer is updated in place.
    def fn(part, rows, values):
        if sharding.memory_kind != "pinned_host":
            return _row_update(part, rows, values, op)
        rows = jax.device_put(rows, jax.memory.Space.Host)
        values = jax.device_put(values, jax.memory.Space.Host)
        with compute_on("device_host"):
            return _row_update(part, rows, values, op)

    return jax.jit(fn, donate_argnums=(0,), out_shardings=sharding)


def donated_update(part: jax.Array, rows, values, op: str = "set",
                   *, bucket: bool = True, out_sharding=None) -> jax.Array:
    """In-place (donated) row scatter: ``part[rows] = values`` reusing
    ``part``'s buffer.  The caller must own ``part`` exclusively (see
    the donation contract above); ``part`` is deleted on return.

    ``op`` is ``"set"`` or ``"add"`` (duplicates accumulate under add;
    set requires distinct rows, as everywhere else in the scatter
    stack).  With ``bucket`` the index/value arrays are padded to
    power-of-two lengths so delta-size churn stays within a bounded
    trace count.  ``out_sharding`` pins the output memory space (the
    ``memory_kind`` backend's pinned-host shards)."""
    if bucket:
        rows, values = pad_to_bucket(rows, values, part.shape[0])
    rows = jnp.asarray(rows)
    values = jnp.asarray(values, part.dtype)
    if out_sharding is not None:
        return _donated_row_update_sharded(op, out_sharding)(
            part, rows, values)
    return _donated_row_update(part, rows, values, op)


@functools.partial(jax.jit, donate_argnums=(0,))
def _donated_kv_update(pool, slots, rows, data):
    # pool: (L, B, T, K, hd); writes data (L, n_slots, n_rows, K, hd)
    # into the [slots x rows] page slabs of every layer at once.
    return pool.at[:, slots[:, None], rows[None, :]].set(data, mode="drop")


def donated_kv_update(pool: jax.Array, slots, rows, data) -> jax.Array:
    """In-place (donated) KV-pool page-slab scatter for ``_retile``:
    pool[:, slots, rows] = data, reusing ``pool``'s buffer.  Same
    exclusive-ownership contract as :func:`donated_update`."""
    return _donated_kv_update(pool, jnp.asarray(slots, jnp.int32),
                              jnp.asarray(rows, jnp.int32),
                              jnp.asarray(data, pool.dtype))

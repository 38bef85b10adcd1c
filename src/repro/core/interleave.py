"""Page-granular N:M tier interleaving of one logical array.

``InterleavedTensor`` is the framework object behind the paper's
weighted-interleave experiments: a logical ``(rows, *feature)`` array
whose pages are distributed across a fast tier and N slow devices
according to a :class:`~repro.core.policy.MemPolicy` (the paper's
testbed exposes three CXL devices from different manufacturers at
once, §4/Table 1).  The tensor holds one page shard per device plus a
page->device map; reads and writes are routed to the owning device,
and embedding-bag reduction (the paper's DLRM §5.2 workload) runs a
reduce per shard and sums — numerically identical to the un-tiered
reduce (see tests/property tests).

Hot paths (the Caption loop's actuation and access costs, ISSUE 5):

* **Shape-stable shards** — with ``headroom > 0`` each device shard is
  capacity-padded by that many page chunks, and a repartition whose new
  per-device page counts fit the existing capacities rewrites only the
  index maps and the moved pages: shard shapes (and the pytree treedef)
  are unchanged, so jitted consumers never retrace across Caption probe
  epochs.  Only when headroom is exhausted does the shard grow (and the
  consumer retrace, once).
* **O(Δ) vectorized repartition** — the planner is numpy index
  arithmetic, and moved pages are coalesced into contiguous
  source-local *runs*, one batched mover :class:`Descriptor` per run
  (route-pure, billed bytes identical to per-page movement).
* **Single-pass routed access** — ``gather_rows``/``update_rows`` with
  concrete indices bucket rows by owning device (argsort), do one
  compact take/scatter per shard over only the rows it owns, and
  inverse-permute: one pass of memory traffic instead of one full pass
  per device.  Traced (jit) calls keep the masked N-pass formulation,
  whose shapes are static.

Memory backends (ISSUE 7 — ``backend=`` on :meth:`from_array`):

* ``modeled`` — every shard is a plain device array; the tier split is
  accounting (ledger + telemetry + perfmodel).  The CPU default.
* ``staged`` — same allocation, but actuation payloads stay device-side
  jax slabs so the mover's double-buffered Pallas ``stream_copy``
  executor moves them (HBM -> VMEM staging -> HBM, overlapped DMAs).
* ``memory_kind`` — slow shards physically live in ``pinned_host``
  memory via JAX memory-kind shardings; fast stays in ``device``.
  Requires a runtime exposing pinned-host memory (TPU/GPU); asking for
  it elsewhere raises (``"auto"`` picks ``modeled`` there).  XLA cannot
  compute on a ``pinned_host`` operand from the device, so reads of a
  slow shard gather the touched rows on the host and move only those to
  HBM, and donated writes scatter on the host in place.

Donation (``donate=`` on the writers/repartitioners): when the caller
provably drops the parent tensor, the stable-path update runs through a
jitted ``donate_argnums`` scatter that reuses the receiving shard's
buffer in place — the last full-shard copy-on-write in the probe-epoch
loop goes away (see :mod:`repro.core.donation` for the contract).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compute_on import compute_on

from repro.core.donation import FULL_SHARD_COPIES, donated_update
from repro.core.ledger import TierLedger
from repro.core.policy import MemPolicy, largest_remainder_split
from repro.core.telemetry import GLOBAL_TELEMETRY, Telemetry

#: shard memory backends (see module docstring).
BACKENDS = ("modeled", "staged", "memory_kind")


def supports_memory_kinds() -> bool:
    """True when the device has memory of its own and the runtime also
    exposes a ``pinned_host`` space to tier into (TPU/GPU runtimes).  A
    CPU device's memory already is host memory, so a ``pinned_host``
    space there is no slower tier."""
    device = jax.devices()[0]
    kinds = {m.kind for m in device.addressable_memories()}
    return device.platform != "cpu" and "pinned_host" in kinds


def resolve_backend(backend: str = "auto") -> str:
    """Resolve a requested backend to one this runtime can honour.

    ``auto`` picks ``memory_kind`` where the runtime has a pinned-host
    memory space and ``modeled`` elsewhere.  An explicit ``memory_kind``
    raises where it cannot be honoured rather than quietly modeling the
    tiers; ``modeled``/``staged`` pass through."""
    if backend == "auto":
        return "memory_kind" if supports_memory_kinds() else "modeled"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of "
                         f"{BACKENDS + ('auto',)}")
    if backend == "memory_kind" and not supports_memory_kinds():
        raise ValueError(
            "backend 'memory_kind' needs a pinned_host memory space, which "
            f"the {jax.default_backend()} runtime does not expose")
    return backend


def _place_part(part, ordinal: int, backend: str) -> jax.Array:
    """A shard (jax or numpy) as a jax array in its memory kind: ``device``
    for the fast tier, ``pinned_host`` for slow devices (``memory_kind``
    backend only; the others keep default device memory)."""
    if backend != "memory_kind":
        return jnp.asarray(part)
    kind = "device" if ordinal == 0 else "pinned_host"
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0],
                                                 memory_kind=kind)
    return jax.device_put(part, sharding)


@jax.jit
def _host_rows(part: jax.Array, idx: jax.Array) -> jax.Array:
    """``part[idx]`` in HBM for a shard held in ``pinned_host`` memory: the
    gather runs on the host, and only the gathered rows cross to HBM."""
    idx = jax.device_put(idx, jax.memory.Space.Host)
    with compute_on("device_host"):
        rows = jnp.take(part, idx, axis=0, mode="clip")
    return jax.device_put(rows, jax.memory.Space.Device)


#: default movement-run length (pages) the minimal-move planner clusters
#: its picks into: one mover Descriptor ships one run, so a Δ-page shift
#: drains ~Δ/RUN descriptors instead of Δ (§6 descriptor batching).  Run
#: STARTS stay evenly spread across the address range, so the interleave
#: discipline holds at run granularity; raise for cheaper actuation,
#: lower toward 1 for finer spreading (1 = legacy page-at-a-time).
DEFAULT_RUN_PAGES = 16

#: gather-crossover cost model (ISSUE 8 satellite), calibrated on the CPU
#: backend: the masked gather pays one eager pass per non-empty shard
#: (fixed dispatch + per-byte XLA work over the WHOLE batch each pass);
#: the bucketed gather pays one host base (route + jnp.asarray hand-back)
#: plus per-row numpy fancy-indexing overhead.  ``choose_gather_path``
#: compares the two estimates per call.
_GATHER_DISPATCH_S = 45e-6  #: per eager-op dispatch, per shard pass
_GATHER_XLA_PER_BYTE = 0.0002e-6  #: masked per-byte cost, per shard pass
_GATHER_HOST_BASE_S = 100e-6  #: bucketed fixed cost (route + hand-back)
_GATHER_HOST_PER_ROW = 0.05e-6  #: numpy fancy-indexing per-row overhead
_GATHER_HOST_PER_BYTE = 0.0003e-6  #: bucketed per-byte copy cost


def device_page_map(assign: np.ndarray, n_devices: int
                    ) -> tuple[np.ndarray, np.ndarray, list[int]]:
    """(device ordinals, local index within owning device, per-device counts).

    The one place the page->device bookkeeping lives: each page's local
    index is its arrival order within its device.  Vectorized (cumsum
    per device) — it runs on every construction and repartition.  Shared
    by construction and repartition here and by the tiered KV cache."""
    dev = np.asarray(assign, np.int8)
    if dev.size and int(dev.max()) >= n_devices:
        raise ValueError(
            f"page assigned to device {int(dev.max())} >= {n_devices}")
    local = np.zeros(len(dev), np.int32)
    counts: list[int] = []
    for d in range(n_devices):
        mask = dev == d
        counts.append(int(mask.sum()))
        if counts[-1]:
            local[mask] = np.cumsum(mask)[mask] - 1
    return dev, local, counts


def tier_page_map(assign: np.ndarray) -> tuple[np.ndarray, np.ndarray, list[int]]:
    """Two-part storage view: devices beyond the second collapse onto the
    slow part, and each page's local index is its arrival order within its
    storage tier (the KV cache's shape-stable fast/slow pools)."""
    assign01 = np.minimum(np.asarray(assign), 1).astype(np.int8)
    return device_page_map(assign01, 2)


def contiguous_runs(values: np.ndarray) -> list[tuple[int, int]]:
    """(start, length) spans where ``values`` increments by exactly 1.

    The run-coalescing primitive: positions whose source locals are
    consecutive form one contiguous slab in the owning shard and ship as
    a single batched mover descriptor."""
    v = np.asarray(values)
    if v.size == 0:
        return []
    breaks = np.nonzero(np.diff(v) != 1)[0] + 1
    starts = np.concatenate(([0], breaks))
    ends = np.concatenate((breaks, [v.size]))
    return [(int(s), int(e - s)) for s, e in zip(starts, ends)]


def route_pure_runs(src: np.ndarray, dst: np.ndarray, loc: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sort moved items by (src, dst, source local) and split them into
    route-pure runs of consecutive locals.

    Returns ``(order, starts, ends)``: ``order`` permutes the inputs into
    run order, and ``[starts[i], ends[i])`` spans run ``i`` within it.
    The ONE place the coalescing rule lives — a run never mixes (src,
    dst) routes and its source locals are adjacent, so it is a single
    contiguous slab of the source pool and ships as one batched
    descriptor.  Shared by ``InterleavedTensor`` and ``TieredKVCache``
    so the two actuation paths can never bill runs differently."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    loc = np.asarray(loc, np.int64)
    if src.size == 0:
        empty = np.zeros(0, np.int64)
        return empty, empty, empty
    order = np.lexsort((loc, dst, src))
    s, d, lo = src[order], dst[order], loc[order]
    key = s * (int(max(s.max(), d.max())) + 2) + d
    brk = np.nonzero((np.diff(key) != 0) | (np.diff(lo) != 1))[0] + 1
    starts = np.concatenate(([0], brk))
    ends = np.concatenate((brk, [order.size]))
    return order, starts, ends


def _policy_device_map(policy, n_pages: int
                       ) -> tuple[np.ndarray, tuple[str, ...]]:
    """Resolve a policy to (page->device ordinals, canonical device names).

    Canonical order is fast first, then the policy's slow tiers in
    declaration order — so ``membind("slow")`` lands every page on device
    1 and a three-device weighted policy yields ordinals 0..3.  The fast
    tier is the first well-known fast name, else — for multi-tier
    policies — the FIRST tier (``from_tier_fractions`` always puts the
    fast home first, and registry fast tiers like ``ddr5-r1`` are not on
    the whitelist)."""
    assign = np.asarray(policy.assign_pages(n_pages))
    tiers = tuple(policy.tiers)
    fast_names = MemPolicy._FAST_NAMES
    fast_tier = next((t for t in tiers if t.lower() in fast_names), None)
    if fast_tier is None and len(tiers) > 1:
        fast_tier = tiers[0]
    if fast_tier is None and len(tiers) == 1:
        # membind on a registry device: infer fast-vs-slow from its KIND
        # (local DRAM/HBM is a fast home; CXL/host/remote are far tiers),
        # so membind('ddr5-r1') is not silently treated as 100% slow when
        # the operator made it the fast tier... and membind('cxl-a') still
        # correctly lands every page on the slow side.
        from repro.core.tiers import DEVICE_REGISTRY
        spec = DEVICE_REGISTRY.get(tiers[0].lower())
        if spec is not None and spec.kind in ("hbm", "ddr_local"):
            fast_tier = tiers[0]

    def is_fast(t: str) -> bool:
        return t == fast_tier or t.lower() in fast_names

    slow_tiers: list[str] = []
    for t in tiers:
        if not is_fast(t) and t not in slow_tiers:
            slow_tiers.append(t)
    names = (fast_tier or "fast",) + (tuple(slow_tiers) or ("slow",))
    dev_of = np.asarray(
        [0 if is_fast(t) else 1 + slow_tiers.index(t) for t in tiers],
        np.int8)
    dev = dev_of[np.minimum(assign, len(tiers) - 1)]
    return dev, names


def resolve_device_names(existing: Sequence[str], n_devices: int,
                         policy_names: Optional[Sequence[str]] = None,
                         fast_tier: Optional[str] = None,
                         slow_tier: Optional[str] = None) -> tuple[str, ...]:
    """Resolve device-ordinal route labels: a policy's names, widened
    with the EXISTING names for higher ordinals (a narrower policy must
    not rename a pinned page's real device), padded with placeholders,
    with the legacy fast/slow overrides on the first two (the two-device
    compatibility path).  Shared by InterleavedTensor and TieredKVCache
    so the two actuation paths can never resolve names differently."""
    names = list(policy_names or existing)
    for n in tuple(existing)[len(names):]:
        names.append(n)
    while len(names) < n_devices:
        names.append(f"slow{len(names)}")
    if fast_tier is not None:
        names[0] = fast_tier
    if slow_tier is not None and len(names) > 1:
        names[1] = slow_tier
    return tuple(names)


def _is_concrete(*arrays) -> bool:
    """True when every array can be materialized host-side (not a jit
    tracer) — the gate between the single-pass bucketed access path and
    the masked shape-static formulation."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class InterleavedTensor:
    """A logical array paged across (fast, slow devices...) along axis 0."""

    #: per-device page shards; ``parts[0]`` is the fast tier's.  With
    #: ``headroom > 0`` each shard is capacity-padded: only the slots the
    #: page->local map points at are valid, the rest is reserve.
    parts: tuple[jax.Array, ...]
    page_device: jax.Array  # (n_pages,) int8: 0 = fast, i >= 1 = slow dev i-1
    page_local: jax.Array  # (n_pages,) int32: page index within its device
    page_rows: int
    rows: int  # logical row count (may be < n_pages * page_rows)
    #: route labels per device ordinal (telemetry/mover tier names).
    device_names: tuple[str, ...] = ("fast", "slow")
    #: capacity padding, in page chunks per device shard.  0 = exact-size
    #: shards (every repartition resizes them — the legacy layout);
    #: > 0 = shape-stable shards (repartitions that fit never reallocate,
    #: so jitted consumers never retrace across Caption probe epochs).
    headroom: int = 0
    #: shard memory backend (see module docstring): ``modeled`` (plain
    #: buffers, accounted tiers), ``staged`` (device-side actuation
    #: payloads through the Pallas migration kernel), or ``memory_kind``
    #: (physical ``pinned_host`` slow shards; TPU/GPU runtimes).
    backend: str = "modeled"

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        children = (tuple(self.parts), self.page_device, self.page_local)
        aux = (self.page_rows, self.rows, self.device_names, self.headroom,
               self.backend)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        parts, page_device, page_local = children
        page_rows, rows, device_names, headroom, backend = aux
        return cls(tuple(parts), page_device, page_local, page_rows, rows,
                   device_names, headroom, backend)

    # -- host-side map cache --------------------------------------------------
    def _host_map(self) -> tuple[np.ndarray, np.ndarray]:
        """Cached numpy (page_device, page_local) — controller reads
        (slow_fraction / device_fractions / weights) happen every epoch
        and must not re-sync the device arrays each time."""
        cached = self.__dict__.get("_host_cache")
        if cached is None:
            cached = (np.asarray(self.page_device),
                      np.asarray(self.page_local))
            self.__dict__["_host_cache"] = cached
        return cached

    def _with_map(self, dev: np.ndarray, local: np.ndarray) -> None:
        """Seed the host cache when the maps were just built host-side."""
        self.__dict__["_host_cache"] = (dev, local)

    def _part_host(self, i: int) -> np.ndarray:
        """Cached host mirror of shard ``i``.

        The shards are immutable jax buffers, so a host copy stays valid
        for the instance's lifetime; repartitions hand the mirrors of
        untouched shards to the child instance, which is what makes the
        shape-stable actuation path O(Δ): only the receiving shard is
        copied, everything else is fancy-indexed through its mirror.
        Mirrors must NEVER be mutated — writers copy first."""
        cache = self.__dict__.get("_parts_host")
        if cache is None:
            cache = self.__dict__["_parts_host"] = [None] * len(self.parts)
        if cache[i] is None:
            cache[i] = np.asarray(self.parts[i])
        return cache[i]

    def _with_parts_host(self, mirrors: list) -> None:
        """Seed the host mirrors (entries may be None for lazy fill)."""
        self.__dict__["_parts_host"] = list(mirrors)

    def _inherit_parts_host(self) -> list:
        cache = self.__dict__.get("_parts_host")
        return list(cache) if cache is not None else [None] * len(self.parts)

    # -- two-device compatibility views --------------------------------------
    @property
    def fast(self) -> jax.Array:
        return self.parts[0]

    @property
    def slow(self) -> jax.Array:
        """The single slow shard (two-device path); ambiguous beyond that."""
        if len(self.parts) > 2:
            raise AttributeError(
                "tensor has multiple slow devices; index .parts directly")
        return self.parts[1]

    @property
    def page_tier(self) -> jax.Array:
        """(n_pages,) int8 0/1 fast-vs-slow view of the device map."""
        return jnp.minimum(self.page_device, 1).astype(jnp.int8)

    # -- construction --------------------------------------------------------
    @classmethod
    def from_array(
        cls,
        array: jax.Array,
        policy: MemPolicy,
        page_rows: int = 256,
        *,
        headroom: int = 0,
        backend: str = "modeled",
        ledger: Optional[TierLedger] = None,
        name: str = "interleaved",
    ) -> "InterleavedTensor":
        backend = resolve_backend(backend)
        rows = array.shape[0]
        n_pages = max(1, math.ceil(rows / page_rows))
        assign, names = _policy_device_map(policy, n_pages)
        dev, page_local, counts = device_page_map(assign, len(names))
        pad_rows = n_pages * page_rows - rows
        feature = array.shape[1:]
        padded = jnp.concatenate(
            [array, jnp.zeros((pad_rows,) + feature, array.dtype)], axis=0
        ) if pad_rows else array
        paged = padded.reshape((n_pages, page_rows) + feature)

        def take_pages(ids, cap: int):
            got = (paged[np.asarray(ids)] if len(ids)
                   else jnp.zeros((0, page_rows) + feature, array.dtype))
            if cap > len(ids):
                pad = jnp.zeros((cap - len(ids), page_rows) + feature,
                                array.dtype)
                got = jnp.concatenate([got, pad]) if len(ids) else pad
            return got

        parts = tuple(
            _place_part(
                take_pages(np.nonzero(dev == i)[0],
                           counts[i] + max(int(headroom), 0))
                .reshape((-1,) + feature),
                i, backend)
            for i in range(len(names)))
        out = cls(
            parts=parts,
            page_device=jnp.asarray(dev, jnp.int8),
            page_local=jnp.asarray(page_local, jnp.int32),
            page_rows=page_rows,
            rows=rows,
            device_names=names,
            headroom=max(int(headroom), 0),
            backend=backend,
        )
        out._with_map(dev, page_local)
        if ledger is not None:
            for i, part in enumerate(parts):
                if part.size:
                    ledger.register(name, names[i],
                                    part.size * part.dtype.itemsize)
        return out

    # -- derived -------------------------------------------------------------
    @property
    def n_pages(self) -> int:
        return self.page_device.shape[0]

    @property
    def n_devices(self) -> int:
        return len(self.parts)

    @property
    def row_bytes(self) -> int:
        f = self.parts[0]
        feat = int(np.prod(f.shape[1:])) if f.ndim > 1 else 1
        return feat * f.dtype.itemsize

    @property
    def capacity_pages(self) -> tuple[int, ...]:
        """Per-device shard capacity in pages (valid + headroom)."""
        return tuple(p.shape[0] // self.page_rows for p in self.parts)

    def valid_page_counts(self) -> tuple[int, ...]:
        """Per-device VALID page counts (what the map actually uses)."""
        dev, _ = self._host_map()
        return tuple(np.bincount(dev, minlength=len(self.parts)).tolist())

    def slow_fraction(self) -> float:
        dev, _ = self._host_map()
        return float((dev >= 1).mean())

    def device_fractions(self) -> dict[str, float]:
        """Per-device page share, keyed by device name."""
        dev, _ = self._host_map()
        return {n: float((dev == i).mean())
                for i, n in enumerate(self.device_names)}

    def weights(self) -> tuple[float, ...]:
        """Per-slow-device page shares (the Caption weight vector)."""
        dev, _ = self._host_map()
        return tuple(float((dev == i).mean())
                     for i in range(1, len(self.parts)))

    # -- addressing ----------------------------------------------------------
    def _route(self, idx: jax.Array):
        """row idx -> (owning device ordinal, local flat row index)."""
        page = idx // self.page_rows
        offset = idx % self.page_rows
        dev = jnp.take(self.page_device, page, mode="clip")
        local_page = jnp.take(self.page_local, page, mode="clip")
        local = local_page * self.page_rows + offset
        return dev, local

    def _route_host(self, idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Host-side :meth:`_route` over the cached maps (no device sync)."""
        dev_map, local_map = self._host_map()
        # clip (not wrap) out-of-range pages, matching the traced path's
        # mode="clip" take semantics
        page = np.clip(idx // self.page_rows, 0, self.n_pages - 1)
        offset = idx % self.page_rows
        dev = dev_map[page]
        local = local_map[page].astype(np.int64) * self.page_rows + offset
        return dev, local

    # -- access --------------------------------------------------------------
    def choose_gather_path(self, n_rows: int) -> str:
        """Size-based crossover: ``"masked"`` or ``"bucketed"`` for a
        concrete gather of ``n_rows`` rows (ISSUE 8 satellite — the
        bucketed path lost to masked at large batches).

        The masked path pays one full eager pass PER NON-EMPTY SHARD
        (dispatch + a batch-sized take/where), so its cost scales with
        shard count and bytes; the bucketed path pays a fixed host base
        (route + the jnp.asarray hand-back) plus per-row fancy-indexing
        overhead, independent of shard count.  The constants are
        calibrated from measured CPU crossovers (2 shards: masked wins
        from ~1-2K rows; 4 shards: bucketed wins through ~4K rows and
        keeps winning at any size once rows are wide).  The chosen path
        is also what ``bench_hotpaths.py`` records in its JSON."""
        shards = sum(1 for p in self.parts if p.shape[0] > 0) or 1
        row_bytes = (int(np.prod(self.parts[0].shape[1:]))
                     * self.parts[0].dtype.itemsize)
        masked_est = shards * (_GATHER_DISPATCH_S
                               + n_rows * row_bytes * _GATHER_XLA_PER_BYTE)
        bucketed_est = (_GATHER_HOST_BASE_S
                        + n_rows * (_GATHER_HOST_PER_ROW
                                    + row_bytes * _GATHER_HOST_PER_BYTE))
        return "bucketed" if bucketed_est < masked_est else "masked"

    def gather_rows(self, idx: jax.Array) -> jax.Array:
        """rows[idx] — routed gather across the device shards.

        Concrete indices pick masked vs bucketed per call through
        :meth:`choose_gather_path`: the bucketed single-pass host gather
        (rows bucketed by owning device, one compact take per shard, no
        per-shard full pass) wins at small/mid batches and on many-shard
        topologies, while the masked N-pass formulation wins at large
        narrow batches where numpy's per-row overhead dominates.  Traced
        indices (inside jit) always use the masked formulation, which is
        shape-static.  The two are value-identical (asserted bit-exact
        by tests/test_hotpaths.py).
        """
        if _is_concrete(idx, self.page_device, *self.parts):
            if self.choose_gather_path(int(np.asarray(idx).size)) == "bucketed":
                return self._gather_rows_bucketed(np.asarray(idx))
            return self._gather_rows_masked(jnp.asarray(idx))
        return self._gather_rows_masked(idx)

    def _gather_rows_masked(self, idx: jax.Array) -> jax.Array:
        dev, local = self._route(idx)
        out = None
        for i, part in enumerate(self.parts):
            if part.shape[0] == 0:
                continue
            if self._on_host(i):
                got = _host_rows(part, local)
            else:
                got = jnp.take(part, local, axis=0, mode="clip")
            if out is None:
                out = got
            else:
                mask = (dev == i)
                mask = mask.reshape(mask.shape + (1,) * (got.ndim - mask.ndim))
                out = jnp.where(mask, got, out)
        if out is None:  # zero-page tensor
            feat = self.parts[0].shape[1:]
            out = jnp.zeros(idx.shape + feat, self.parts[0].dtype)
        return out

    def _gather_rows_bucketed(self, idx: np.ndarray) -> jax.Array:
        # Host-side numpy on purpose: index shapes change call to call,
        # so staying in XLA would recompile the gather per shape; numpy
        # fancy indexing is the one-pass copy with zero compile cost on
        # the CPU-modeled backend.
        feat = self.parts[0].shape[1:]
        dtype = self.parts[0].dtype
        flat = np.asarray(idx).ravel()
        if flat.size == 0 or all(p.shape[0] == 0 for p in self.parts):
            return jnp.zeros(idx.shape + feat, dtype)
        dev, local = self._route_host(flat)
        out = np.empty((flat.size,) + feat, dtype)
        for i, part in enumerate(self.parts):
            mask = dev == i
            if not mask.any():
                continue  # shard untouched: no gather pass at all
            view = self._part_host(i)
            rows = np.minimum(local[mask], max(view.shape[0] - 1, 0))
            out[mask] = view[rows]
        return jnp.asarray(out).reshape(idx.shape + feat)

    def _scatter(self, idx: jax.Array, values: jax.Array, op: str,
                 donate: bool = False) -> "InterleavedTensor":
        if _is_concrete(idx, values, self.page_device, *self.parts):
            return self._scatter_bucketed(np.asarray(idx), values, op,
                                          donate=donate)
        return self._scatter_masked(idx, values, op)

    @staticmethod
    def _np_number(dtype) -> bool:
        """True when numpy can index-assign/accumulate this dtype natively
        (extension dtypes like bfloat16 fall back to the masked path)."""
        try:
            return np.issubdtype(np.dtype(dtype), np.number)
        except TypeError:
            return False

    def _scatter_masked(self, idx: jax.Array, values: jax.Array, op: str
                        ) -> "InterleavedTensor":
        dev, local = self._route(idx)
        parts = []
        for i, part in enumerate(self.parts):
            if part.shape[0] == 0:
                parts.append(part)
                continue
            # Out-of-device indices are pushed out of bounds and dropped.
            p_idx = jnp.where(dev == i, local, part.shape[0])
            ref = part.at[p_idx]
            parts.append(ref.set(values, mode="drop") if op == "set"
                         else ref.add(values, mode="drop"))
        return dataclasses.replace(self, parts=tuple(parts))

    def _on_host(self, i: int) -> bool:
        """True when shard ``i`` lives in ``pinned_host`` memory."""
        return self.backend == "memory_kind" and i > 0

    def _donate_sharding(self, i: int):
        """out_sharding pin for donated updates (memory_kind shards only)."""
        if self.backend != "memory_kind":
            return None
        return self.parts[i].sharding

    def _scatter_bucketed(self, idx: np.ndarray, values: jax.Array, op: str,
                          donate: bool = False) -> "InterleavedTensor":
        # Same rationale as the bucketed gather: numpy fancy assignment
        # per owning shard, no XLA recompiles on changing index shapes.
        # With ``donate`` the per-shard update is the jitted donated
        # scatter instead — the shard buffer is patched in place, no
        # full copy-on-write (caller drops the parent; see
        # repro.core.donation for the contract).
        feat = self.parts[0].shape[1:]
        if (op == "add" and not donate
                and not self._np_number(self.parts[0].dtype)):
            return self._scatter_masked(jnp.asarray(idx), values, op)
        flat = np.asarray(idx).ravel()
        vals = np.asarray(values).reshape((flat.size,) + feat)
        dev, local = self._route_host(flat)
        parts = list(self.parts)
        mirrors = self._inherit_parts_host()
        for i, part in enumerate(self.parts):
            if part.shape[0] == 0:
                continue
            mask = dev == i
            if not mask.any():
                continue  # shard untouched: no scatter pass at all
            rows = local[mask]
            keep = rows < part.shape[0]
            if donate:
                # Release live zero-copy host views of the receiving
                # buffer first: any external reference blocks XLA input/
                # output aliasing and donation silently degrades to a
                # full copy (repro.core.donation VIEW HAZARD).
                mirrors[i] = None
                cache = self.__dict__.get("_parts_host")
                if cache is not None:
                    cache[i] = None
                new_jax = donated_update(
                    part, rows[keep], vals[mask][keep], op,
                    out_sharding=self._donate_sharding(i))
                parts[i] = new_jax
                mirrors[i] = np.asarray(new_jax)
                continue
            FULL_SHARD_COPIES.bump()
            new_part = self._part_host(i).copy()  # one writable copy
            if op == "set":
                new_part[rows[keep]] = vals[mask][keep]
            else:
                np.add.at(new_part, rows[keep], vals[mask][keep])
            parts[i] = _place_part(new_part, i, self.backend)
            mirrors[i] = new_part
        out = dataclasses.replace(self, parts=tuple(parts))
        out._with_parts_host(mirrors)
        return out

    def update_rows(self, idx: jax.Array, values: jax.Array, *,
                    donate: bool = False) -> "InterleavedTensor":
        """Functional scatter-set of ``values`` at row ``idx``.

        ``donate=True`` patches the receiving shards in place through the
        jitted donated scatter — only valid when the caller drops ``self``
        (and every ancestor aliasing its shards) after the call."""
        return self._scatter(idx, values, "set", donate)

    def add_rows(self, idx: jax.Array, values: jax.Array, *,
                 donate: bool = False) -> "InterleavedTensor":
        return self._scatter(idx, values, "add", donate)

    def bag_reduce(
        self,
        indices: jax.Array,  # (batch, bag)
        weights: Optional[jax.Array] = None,  # (batch, bag)
        reduce_fn: Optional[Callable] = None,
    ) -> jax.Array:
        """Embedding-bag sum across all device shards (DLRM §5.2 reduction).

        ``reduce_fn(table, indices, weights) -> (batch, feature)`` lets the
        Pallas ``embedding_reduce`` kernel slot in; default is pure jnp.
        Rows owned by another device contribute weight 0 to each shard, so
        the per-shard partials sum to the un-tiered reduction exactly.  A
        ``pinned_host`` shard first gathers the looked-up rows on the host;
        ``reduce_fn`` then reduces those rows in HBM.
        """
        if weights is None:
            weights = jnp.ones(indices.shape, self.parts[0].dtype)
        dev, local = self._route(indices)
        if reduce_fn is None:
            reduce_fn = _jnp_bag_reduce
        out = None
        for i, part in enumerate(self.parts):
            if part.shape[0] == 0:
                continue
            w_i = jnp.where(dev == i, weights, 0).astype(part.dtype)
            local_i = jnp.minimum(local, part.shape[0] - 1)
            if self._on_host(i):
                part = _host_rows(part, local_i.reshape(-1))
                local_i = jnp.arange(local_i.size).reshape(local_i.shape)
            partial = reduce_fn(part, local_i, w_i)
            out = partial if out is None else out + partial
        if out is None:  # zero-row tensor
            feat = self.parts[0].shape[1:]
            out = jnp.zeros((indices.shape[0],) + feat, self.parts[0].dtype)
        return out

    # -- migration (TPP-style page moves; used by elastic re-planning) -------
    def migrate_pages(self, page_ids: np.ndarray, to_slow: bool) -> "InterleavedTensor":
        """Move whole pages between tiers (host-side; not jit-traceable)."""
        dense = np.asarray(self.to_array())
        dev = np.asarray(self.page_device).copy()
        dev[np.asarray(page_ids)] = 1 if to_slow else 0
        policy_like = _ExplicitAssignment(dev, self.device_names)
        return InterleavedTensor.from_array(
            jnp.asarray(dense), policy_like, self.page_rows,
            headroom=self.headroom, backend=self.backend,
        )

    def repartition(
        self,
        policy: MemPolicy,
        *,
        mover=None,  # Optional[BulkMover]
        fast_tier: Optional[str] = None,
        slow_tier: Optional[str] = None,
        telemetry: Telemetry = GLOBAL_TELEMETRY,
        source: Optional[str] = None,
        lane: Optional[int] = None,
        donate: bool = False,
    ) -> "InterleavedTensor":
        """Re-tier under ``policy``, migrating ONLY the delta pages.

        The Caption controller's actuation path: diff the current
        page->device map against the policy's and ship just the changed
        pages between devices — through the
        :class:`~repro.core.mover.BulkMover` when one is given (batched,
        cache-bypass descriptors, writer-limited), else accounted directly
        to telemetry.  Unchanged pages never cross the interconnect, so
        inter-device traffic equals ``delta_pages * page_bytes`` exactly
        (asserted by benchmarks/fig11_caption.py).  Every move is billed
        to its real ``(src_device, dst_device)`` route — a page hopping
        between two slow devices is the paper's C2C traffic, not
        fast-tier churn.

        ``fast_tier``/``slow_tier`` override the first two route labels
        (the two-device compatibility path, e.g. hbm/host on v5e).

        ``donate=True`` lets the stable path patch receiving shards in
        place (jitted donated scatter, zero full-shard copies) — only
        valid when the caller drops ``self`` after the call (the Caption
        actuation pattern ``it = it.repartition(...)``).

        Numerically a no-op: ``to_array()`` before == after.
        """
        n = self.n_pages
        new_dev, names = _policy_device_map(policy, n)
        # Widen with the tensor's EXISTING names: a narrower policy on a
        # wider tensor must keep billing the higher ordinals' real
        # devices, not rename them to placeholders.
        names = resolve_device_names(
            self.device_names, max(len(names), len(self.parts)), names,
            fast_tier, slow_tier)
        return self._reassign(new_dev, names, mover=mover,
                              telemetry=telemetry, source=source, lane=lane,
                              donate=donate)

    def reassign_pages(self, new_dev: np.ndarray, *,
                       device_names: Optional[Sequence[str]] = None,
                       mover=None, telemetry: Telemetry = GLOBAL_TELEMETRY,
                       source: Optional[str] = None,
                       lane: Optional[int] = None,
                       donate: bool = False) -> "InterleavedTensor":
        """Re-tier to an EXPLICIT page -> device-ordinal map.

        The semantic-placement entry point (``core/hotness.py``): a
        caller that knows *what* each page holds hands the exact map
        instead of a share vector, and the move still rides the normal
        O(Δ) path — run-coalesced route-pure descriptors, shape-stable
        shards under ``headroom``, optional donation.  A map equal to
        the current assignment returns ``self`` unchanged."""
        new_dev = np.asarray(new_dev, np.int8)
        if new_dev.shape != (self.n_pages,):
            raise ValueError(
                f"assignment has {new_dev.shape} pages, tensor has "
                f"{self.n_pages}")
        if new_dev.size and int(new_dev.min()) < 0:
            raise ValueError("negative device ordinal")
        n_devices = max(len(self.parts), int(new_dev.max(initial=0)) + 1)
        names = resolve_device_names(self.device_names, n_devices,
                                     device_names)
        return self._reassign(new_dev, names, mover=mover,
                              telemetry=telemetry, source=source, lane=lane,
                              donate=donate)

    # -- the vectorized O(Δ) actuation core ----------------------------------
    def _move_runs(self, delta: np.ndarray, old_dev: np.ndarray,
                   old_local: np.ndarray, new_dev: np.ndarray
                   ) -> list[tuple[int, int, np.ndarray, int]]:
        """Coalesce the delta pages into route-pure movement runs.

        Returns ``(src_dev, dst_dev, page_ids, src_local_start)`` tuples
        where the pages' source locals are consecutive — each run is one
        contiguous slab of its source shard and ships as ONE batched
        descriptor.  Sorting is (src, dst, src_local), so coalescing
        never mixes routes and billed bytes are exactly
        ``delta_pages * page_bytes``."""
        if delta.size == 0:
            return []
        order, starts, ends = route_pure_runs(
            old_dev[delta], new_dev[delta], old_local[delta])
        pages = delta[order]
        src = old_dev[delta][order]
        dst = new_dev[delta][order]
        loc = old_local[delta][order]
        return [(int(src[s]), int(dst[s]), pages[s:e], int(loc[s]))
                for s, e in zip(starts, ends)]

    def _ship_runs(self, runs, names, *, mover, telemetry, source, lane
                   ) -> None:
        """Meter the movement runs: one batched descriptor per run
        through the mover, or one telemetry record per run."""
        if not runs:
            return
        page_bytes = self.page_rows * self.row_bytes

        def route_name(d: int) -> str:
            return names[d] if d < len(names) else f"dev{d}"

        if mover is not None:
            from repro.core.mover import LANE_BULK, Descriptor
            pr = self.page_rows

            def slab(s: int, l0: int, n_pages: int):
                # modeled backend ships zero-copy host-mirror views, and
                # so do pinned_host shards (the page gather mirrors them
                # anyway); device shards of the staged / memory_kind
                # backends keep the slab device-side so the mover's
                # double-buffered stream_copy executor actually moves it.
                if self.backend == "modeled" or self._on_host(s):
                    return self._part_host(s)[l0 * pr: (l0 + n_pages) * pr]
                return self.parts[s][l0 * pr: (l0 + n_pages) * pr]

            descs = [
                Descriptor(
                    src_tier=route_name(s),
                    dst_tier=route_name(d),
                    payload=slab(s, l0, len(pages)),
                    lane=LANE_BULK if lane is None else lane,
                    source=source,
                )
                for s, d, pages, l0 in runs
            ]
            mover.submit(descs)
            if mover.asynchronous:
                mover.wait_all()
        else:
            for s, d, pages, _ in runs:
                telemetry.record_move(route_name(s), route_name(d),
                                      page_bytes * len(pages), 0.0,
                                      source=source)

    def _gather_pages(self, page_ids: np.ndarray, old_dev: np.ndarray,
                      old_local: np.ndarray) -> np.ndarray:
        """(len(page_ids), page_rows, *feature) page data, one compact
        fancy-indexed copy per source shard (vectorized; no per-page
        Python, no XLA recompiles on changing delta shapes)."""
        pr = self.page_rows
        feature = self.parts[0].shape[1:]
        out = np.empty((page_ids.size, pr) + feature, self.parts[0].dtype)
        if page_ids.size == 0:
            return out
        src = old_dev[page_ids]
        for s in np.unique(src):
            mask = src == s
            view = self._part_host(int(s)).reshape((-1, pr) + feature)
            out[mask] = view[old_local[page_ids[mask]]]
        return out

    def _reassign(self, new_dev: np.ndarray, names: tuple[str, ...], *,
                  mover=None, telemetry: Telemetry = GLOBAL_TELEMETRY,
                  source: Optional[str] = None,
                  lane: Optional[int] = None,
                  donate: bool = False) -> "InterleavedTensor":
        n = self.n_pages
        new_dev = np.asarray(new_dev, np.int8)
        old_dev, old_local = self._host_map()
        n_devices = max(len(names), len(self.parts),
                        int(new_dev.max(initial=0)) + 1)
        delta = np.nonzero(new_dev != old_dev)[0]
        if delta.size == 0 and n_devices == len(self.parts):
            return self

        new_counts = np.bincount(new_dev, minlength=n_devices)
        caps = self.capacity_pages

        # Bill / ship the movement first (payloads slice the CURRENT
        # shards): one route-pure batched descriptor per contiguous run.
        runs = self._move_runs(delta, old_dev, old_local, new_dev)
        self._ship_runs(runs, names, mover=mover, telemetry=telemetry,
                        source=source, lane=lane)

        def route_name(d: int) -> str:
            return names[d] if d < len(names) else f"dev{d}"

        stable = (self.headroom > 0 and n_devices == len(self.parts)
                  and all(int(new_counts[d]) <= caps[d]
                          for d in range(n_devices)))
        if stable:
            out = self._reassign_stable(delta, old_dev, old_local, new_dev,
                                        donate=donate)
        else:
            out = self._reassign_rebuild(old_dev, old_local, new_dev,
                                         n_devices)
        final = dataclasses.replace(
            out, device_names=tuple(route_name(d) for d in range(n_devices)))
        final._with_map(*out._host_map())
        final._with_parts_host(out._inherit_parts_host())
        return final

    def _reassign_stable(self, delta: np.ndarray, old_dev: np.ndarray,
                         old_local: np.ndarray, new_dev: np.ndarray,
                         donate: bool = False) -> "InterleavedTensor":
        """Shape-stable fast path: every moved page lands in a free slot
        of its destination shard — shard shapes, the treedef, and every
        unmoved page's slot are untouched, so jitted consumers keep their
        traces.  Planning, index updates, and metered movement are all
        O(Δ).  Materializing the functional update is either one
        copy-on-write of each RECEIVING shard (non-receiving shards are
        reused as-is), or — with ``donate`` — a jitted donated scatter
        that patches the receiving shard's buffer in place: zero full
        copies, O(Δ) rows written (the caller must drop the parent).

        ORDERING HAZARD: a leaving page's old slot counts as free in its
        shard, so an in-place write could clobber it before another
        destination gathers it — therefore every moved page's data is
        gathered into staging FIRST, then all writes happen."""
        pr = self.page_rows
        new_local = old_local.copy()
        parts = list(self.parts)
        mirrors = self._inherit_parts_host()
        caps = self.capacity_pages
        feat = self.parts[0].shape[1:]
        recv = new_dev[delta]
        data_all = self._gather_pages(delta, old_dev, old_local)
        for d in np.unique(recv):
            sel = recv == d
            incoming = delta[sel]
            # free slots = capacity minus the slots kept by staying pages
            staying = (old_dev == d) & (new_dev == d)
            used = np.zeros(caps[int(d)], bool)
            used[old_local[staying]] = True
            free = np.nonzero(~used)[0]
            slots = free[: incoming.size]
            new_local[incoming] = slots.astype(np.int32)
            data = data_all[sel]
            if donate:
                rows = (slots[:, None].astype(np.int64) * pr
                        + np.arange(pr)).reshape(-1)
                # Drop host views of the receiving buffer before the
                # donated call — a live view blocks the in-place alias
                # (repro.core.donation VIEW HAZARD).  ``data_all`` is a
                # fancy-indexed copy, so staging survives the release.
                mirrors[int(d)] = None
                cache = self.__dict__.get("_parts_host")
                if cache is not None:
                    cache[int(d)] = None
                new_jax = donated_update(
                    parts[int(d)], rows, data.reshape((-1,) + feat), "set",
                    out_sharding=self._donate_sharding(int(d)))
                parts[int(d)] = new_jax
                mirrors[int(d)] = np.asarray(new_jax)
                continue
            FULL_SHARD_COPIES.bump()
            new_part = self._part_host(int(d)).copy().reshape(
                (-1, pr) + data.shape[2:])
            new_part[slots] = data
            new_flat = new_part.reshape((-1,) + data.shape[2:])
            parts[int(d)] = _place_part(new_flat, int(d), self.backend)
            mirrors[int(d)] = new_flat
        out = dataclasses.replace(
            self,
            parts=tuple(parts),
            page_device=jnp.asarray(new_dev, jnp.int8),
            page_local=jnp.asarray(new_local, jnp.int32),
        )
        out._with_map(new_dev, new_local)
        out._with_parts_host(mirrors)
        return out

    def _reassign_rebuild(self, old_dev: np.ndarray, old_local: np.ndarray,
                          new_dev: np.ndarray, n_devices: int
                          ) -> "InterleavedTensor":
        """Exact-size (or grow) path: rebuild each shard at its new count
        plus headroom, gathering every device's pages in one vectorized
        take per (dst, src) pair.  This is the path that changes shapes —
        jitted consumers retrace once, by design (headroom exhausted or
        the device set widened)."""
        pr = self.page_rows
        feature = self.parts[0].shape[1:]
        dtype = self.parts[0].dtype
        dev2, local2, counts = device_page_map(new_dev, n_devices)
        parts = []
        mirrors: list = []
        for d in range(n_devices):
            cap = counts[d] + self.headroom
            if cap == 0:
                empty = np.zeros((0,) + tuple(feature), dtype)
                parts.append(_place_part(empty, d, self.backend))
                mirrors.append(empty)
                continue
            pages_d = np.nonzero(dev2 == d)[0]  # page-id order == rank order
            data = np.zeros((cap, pr) + tuple(feature), dtype)
            data[: counts[d]] = self._gather_pages(pages_d, old_dev,
                                                   old_local)
            flat = data.reshape((-1,) + tuple(feature))
            FULL_SHARD_COPIES.bump()
            parts.append(_place_part(flat, d, self.backend))
            mirrors.append(flat)
        out = dataclasses.replace(
            self,
            parts=tuple(parts),
            page_device=jnp.asarray(dev2, jnp.int8),
            page_local=jnp.asarray(local2, jnp.int32),
        )
        out._with_map(dev2, local2)
        out._with_parts_host(mirrors)
        return out

    def repartition_fraction(self, fraction: float, **kwargs
                             ) -> "InterleavedTensor":
        """Re-tier to ``fraction`` slow with the minimal page delta
        (two-device path: the single slow device absorbs the fraction)."""
        return self.repartition_weights((float(fraction),), **kwargs)

    def repartition_weights(self, weights: Sequence[float], *,
                            mover=None, fast_tier: Optional[str] = None,
                            slow_tier: Optional[str] = None,
                            device_names: Optional[Sequence[str]] = None,
                            telemetry: Telemetry = GLOBAL_TELEMETRY,
                            source: Optional[str] = None,
                            lane: Optional[int] = None,
                            run_pages: int = DEFAULT_RUN_PAGES,
                            donate: bool = False
                            ) -> "InterleavedTensor":
        """Re-tier to a per-slow-device weight vector with minimal moves.

        ``weights[i]`` is the target page share of slow device ``i``; the
        fast tier keeps the remainder.  Unlike building an N:M policy —
        whose round-robin pattern can disagree with the current map on far
        more pages than the share delta — this flips exactly the surplus/
        deficit page counts, clustered into evenly spread runs of up to
        ``run_pages`` consecutive pages so the mover drains O(runs)
        batched descriptors instead of O(pages).  A weight vector that
        rounds to the current per-device page counts is a true no-op: the
        same object is returned and no mover work is enqueued."""
        n_devices = max(len(self.parts), len(weights) + 1)
        dev, _ = self._host_map()
        new_dev = minimal_delta_weights(dev, tuple(weights), n_devices,
                                        run_pages=run_pages)
        if new_dev is None:  # rounds to the current assignment: no-op
            return self
        names = resolve_device_names(self.device_names, n_devices,
                                     device_names, fast_tier, slow_tier)
        return self._reassign(new_dev, names, mover=mover,
                              telemetry=telemetry, source=source, lane=lane,
                              donate=donate)

    def drain_device(self, device, **kwargs) -> "InterleavedTensor":
        """Move every page off one slow device (elastic hot-remove drain).

        ``device`` is a slow-device ordinal (>= 1) or its name.  The
        departing share is redistributed over the surviving slow devices
        proportionally to their current shares (the fast tier absorbs it
        when no survivor holds pages), and the move rides the normal
        minimal-delta repartition path: run-coalesced LANE_BULK
        descriptors on real (dead device -> survivor) routes.  Keyword
        arguments forward to :meth:`repartition_weights`."""
        if isinstance(device, str):
            if device not in self.device_names:
                raise KeyError(device)
            i = self.device_names.index(device)
        else:
            i = int(device)
        if not 1 <= i < self.n_devices:
            raise KeyError(device)
        cur = list(self.weights())
        departing, cur[i - 1] = cur[i - 1], 0.0
        rest = sum(cur)
        if departing > 0 and rest > 0:
            cur = [w + departing * w / rest for w in cur]
        return self.repartition_weights(tuple(cur), **kwargs)

    def to_array(self) -> jax.Array:
        """Materialize the logical array (tests / checkpointing)."""
        idx = jnp.arange(self.rows)
        return self.gather_rows(idx)

    # -- accounting -----------------------------------------------------------
    def traffic_bytes(self, idx: np.ndarray) -> dict[str, int]:
        """Bytes touched per device for a concrete index batch (host-side)."""
        page = np.asarray(idx).ravel() // self.page_rows
        dev_map, _ = self._host_map()
        dev = dev_map[np.minimum(page, self.n_pages - 1)]
        out = {}
        for i, name in enumerate(self.device_names):
            out[name] = int((dev == i).sum()) * self.row_bytes
        # two-device compatibility keys
        out.setdefault("fast", out.get(self.device_names[0], 0))
        out.setdefault("slow", sum(
            int((dev == i).sum()) * self.row_bytes
            for i in range(1, len(self.parts))))
        return out

    def record_gather(self, idx: np.ndarray, seconds: float,
                      telemetry: Telemetry = GLOBAL_TELEMETRY) -> None:
        t = self.traffic_bytes(idx)
        for i, name in enumerate(self.device_names):
            telemetry.record_move(name, "engine", t.get(name, 0), seconds)


class _ExplicitAssignment:
    """Adapter: a fixed page->device map with the MemPolicy interface."""

    def __init__(self, assignment: np.ndarray,
                 tiers: Sequence[str] = ("fast", "slow")):
        self._assignment = np.asarray(assignment).astype(np.int8)
        self.tiers = tuple(tiers)

    def assign_pages(self, n_pages: int) -> np.ndarray:
        if n_pages != len(self._assignment):
            raise ValueError("page count mismatch")
        return self._assignment

    def page_is_slow(self, n_pages: int) -> np.ndarray:
        return self.assign_pages(n_pages) >= 1


def _round_targets(weights: tuple[float, ...], n_pages: int) -> list[int]:
    """Per-slow-device page targets by largest-remainder rounding.

    The total slow count is ``round(sum(weights) * n)`` — identical to the
    scalar path's rounding — then split so the per-device counts sum to it
    exactly (plain per-device rounding can create or destroy pages)."""
    w = [min(max(float(x), 0.0), 1.0) for x in weights]
    total = min(sum(w), 1.0)
    want = int(round(total * n_pages))
    base, _ = largest_remainder_split([x * n_pages for x in w], want)
    return base


def _spread_run_picks(n_cands: int, k: int, run_pages: int) -> np.ndarray:
    """Indices (into a candidate list of length ``n_cands``) of ``k``
    picks grouped into evenly spaced runs of up to ``run_pages``
    consecutive candidates.

    The movement-coalescing compromise: perfectly even per-page spreading
    (stride n/k) keeps the interleave discipline but makes every moved
    page its own mover descriptor; clustering the picks into short runs
    whose *starts* stay evenly spread keeps the access interleave nearly
    uniform while letting the actuator ship each run as one contiguous
    batched descriptor."""
    if k >= n_cands:
        return np.arange(n_cands)
    n_runs = max(1, -(-k // max(run_pages, 1)))
    picked = np.zeros(n_cands, bool)
    taken = 0
    prev_end = 0
    for j in range(n_runs):
        want = (k - taken + (n_runs - j - 1)) // (n_runs - j)  # ceil spread
        start = max((j * n_cands) // n_runs, prev_end)
        end = min(start + want, n_cands)
        picked[start:end] = True
        taken += end - start
        prev_end = end
    if taken < k:  # dense move: fill from the unpicked complement
        rest = np.nonzero(~picked)[0][: k - taken]
        picked[rest] = True
    return np.nonzero(picked)[0]


def minimal_delta_weights(current: np.ndarray, weights: tuple[float, ...],
                          n_devices: int, *,
                          run_pages: int = DEFAULT_RUN_PAGES
                          ) -> Optional[np.ndarray]:
    """New page->device map hitting ``weights`` with the FEWEST moves.

    Returns ``None`` when the targets round to the current per-device
    counts (the no-op guarantee: callers must not churn page ids or
    enqueue empty-delta mover work).  Surplus pages are released in
    evenly spread *runs* of up to ``run_pages`` consecutive pages — each
    run is a contiguous slab of its device and ships as one batched
    mover descriptor — and the runs are dealt to deficit devices
    round-robin, so each deficit device's new pages stay spread across
    the address range (clustered pages would serialize one device on
    strided access).  ``run_pages=1`` recovers the legacy page-at-a-time
    even spreading exactly."""
    cur = np.asarray(current, np.int8)
    n = len(cur)
    targets = _round_targets(tuple(weights), n)
    targets += [0] * (n_devices - 1 - len(targets))
    counts = np.bincount(cur, minlength=n_devices)
    target_all = [n - sum(targets)] + list(targets)
    if all(int(counts[d]) == target_all[d] for d in range(n_devices)):
        return None
    out = cur.copy()
    # Release surplus pages as evenly spread runs within each device.  A
    # run is contiguous in the device's CANDIDATE order — i.e. in its
    # source locals when those are rank-ordered — which is exactly what
    # the actuator can ship as one contiguous-slab descriptor.
    runs_list: list[np.ndarray] = []
    for d in range(n_devices):
        surplus = int(counts[d]) - target_all[d]
        if surplus <= 0:
            continue
        cands = np.nonzero(cur == d)[0]
        picks = _spread_run_picks(len(cands), surplus, run_pages)
        for start, length in contiguous_runs(picks):
            runs_list.append(cands[picks[start: start + length]])
    # Deal whole runs to deficit devices, round-robin, so each deficit
    # device's new pages stay spread across the address range AND every
    # run stays route-pure (one (src, dst) per run; split only when a
    # deficit fills mid-run).
    runs_list.sort(key=lambda a: int(a[0]))
    deficits = [[d, target_all[d] - int(counts[d])]
                for d in range(n_devices) if target_all[d] > int(counts[d])]
    k = 0
    for run in runs_list:
        offset = 0
        while offset < len(run):
            entry = deficits[k % len(deficits)]
            if entry[1] <= 0:
                deficits.pop(k % len(deficits))
                continue
            take = min(entry[1], len(run) - offset)
            out[run[offset: offset + take]] = entry[0]
            entry[1] -= take
            offset += take
            k += 1
    return out


def minimal_delta_assignment(current: np.ndarray, fraction: float) -> np.ndarray:
    """Two-device view of :func:`minimal_delta_weights`.

    The Caption actuation helper: two N:M interleave patterns at nearby
    ratios can disagree on far more pages than the ratio delta, so the
    controller flips exactly ``|target - current|`` pages instead,
    spreading the flipped pages evenly.  When ``fraction`` rounds to the
    current slow-page count the current assignment is returned unchanged
    (no phantom page-id churn)."""
    cur = np.asarray(current, np.int8)
    out = minimal_delta_weights(np.minimum(cur, 1), (float(fraction),), 2)
    return cur.copy() if out is None else out


def _jnp_bag_reduce(table: jax.Array, indices: jax.Array, weights: jax.Array):
    """(batch, bag) weighted gather-sum reference; oracle for the kernel."""
    gathered = jnp.take(table, indices, axis=0)  # (batch, bag, feature)
    return jnp.einsum("bkf,bk->bf", gathered, weights.astype(table.dtype))

"""Tiered, page-interleaved KV cache + decode step (the Redis §5.1 analogue).

The KV time axis is split into pages placed across (fast, slow...) devices
by a MemPolicy — the paper's N:M weighted interleave applied to serving
state.  Decode reads each page from the device pool that holds it, in
position order (``TieredKVCache.ordered``); per-step per-tier byte counts
feed the perfmodel, from which the benchmarks reproduce the paper's
p99/QPS curves (modeled, not measured).

Placement is **per slot**: each batch slot carries its own page->device
map, so a latency-SLO request can pin its pages fast (Fig. 7: any CXL
fraction hurts a µs-SLO app) while batch-class neighbors tolerate slow
pages.  Pinned slots are excluded from ``repartition_fraction`` — the
Caption loop only tunes the batch-class population.

Physical layout (ISSUE 7): storage is **per-device pools** — one
``(L, B, T_d, K, hd)`` K/V pool pair per device ordinal, so storage
bytes match the per-device accounting (``read_bytes_per_device``)
instead of collapsing every slow device onto one shared pool.  The fast
pool is sized for ALL pages (the fast tier is the home tier); each slow
pool holds its own pages plus ``slow_headroom`` pages of capacity.  A
retile whose per-device page counts fit the held capacities takes the
**O(Δ) stable path**: moved pages land in free slots of their
destination pool (gather-first, then write), unreceiving pools are
reused as-is, and with ``donate=True`` the receiving pools are patched
in place through the jitted donated scatter — zero full-pool copies.
Only when a pool outgrows its capacity (or the device set changes) does
the legacy full rebuild run, re-ranking locals and re-padding by the
headroom (jitted decode retraces once, by design).

Applies to the uniform-attention (dense/vlm/moe-attention) families;
recurrent state (rwkv/rglru) is latency-bound and planner-pinned fast.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.donation import FULL_SHARD_COPIES, donated_kv_update
from repro.core.interleave import (_policy_device_map, minimal_delta_weights,
                                   resolve_device_names, route_pure_runs)
from repro.core.mover import LANE_BULK, LANE_LATENCY
from repro.core.policy import MemPolicy
from repro.core.telemetry import GLOBAL_TELEMETRY
from repro.models import attention as attn
from repro.models.common import apply_norm, dtype_of, mlp_apply
from repro.serving.prefix_cache import NO_PAGE, UNALLOCATED, PrefixBlock

_INT32_MAX = np.iinfo(np.int32).max


def _kv_layout_rows(assign: np.ndarray, page_t: int):
    """LEGACY two-pool storage view of a (B, n_pages) page->tier map:
    local indices, shared part sizes, and per-slot per-part global
    positions (INT32_MAX pads never validate in the attention masks).

    Physical storage is per-device (:func:`_kv_device_layout_rows`)
    since ISSUE 7; this two-tier collapse remains the reference layout
    the per-device one generalizes (equivalence with the per-slot
    ``tier_page_map`` walk is asserted by tests/test_hotpaths.py).

    Fully vectorized (argsort/cumsum over the whole B x P map)."""
    assign = np.asarray(assign)
    B, P = assign.shape
    assign01 = np.minimum(assign, 1).astype(np.int8)
    is_slow = assign01.astype(bool)
    # local = rank of the page within its tier, in page order (the same
    # arrival-order discipline tier_page_map uses per slot)
    fast_rank = np.cumsum(~is_slow, axis=1) - 1
    slow_rank = np.cumsum(is_slow, axis=1) - 1
    local = np.where(is_slow, slow_rank, fast_rank).astype(np.int32)
    n_slow = is_slow.sum(axis=1).astype(np.int64)
    Tf = P * page_t
    Ts = int(n_slow.max(initial=0)) * page_t
    # global positions sorted by (tier, page): fast pages' spans first.
    order = np.argsort(assign01, axis=1, kind="stable")
    allpos = (order[:, :, None] * page_t
              + np.arange(page_t)).reshape(B, Tf).astype(np.int32)
    col = np.arange(Tf)
    fast_len = (P - n_slow)[:, None] * page_t
    pos_fast = np.where(col[None, :] < fast_len, allpos, _INT32_MAX)
    if Ts:
        cols = np.arange(Ts)
        gidx = np.minimum(fast_len + cols[None, :], Tf - 1)
        pos_slow = np.where(cols[None, :] < n_slow[:, None] * page_t,
                            np.take_along_axis(allpos, gidx, axis=1),
                            _INT32_MAX)
    else:
        pos_slow = np.zeros((B, 0), np.int32)
    return (assign01, local, Tf, Ts,
            pos_fast.astype(np.int32), pos_slow.astype(np.int32))


def _kv_device_layout_rows(assign: np.ndarray, page_t: int, n_devices: int):
    """Per-DEVICE physical layout for a (B, n_pages) page->device map.

    Returns ``(local, counts, pos_list)``: ``local[b, p]`` is page p's
    rank within its owning device (page order — the rank-order
    discipline every full rebuild restores), ``counts[d, b]`` the page
    count of device d in slot b, and ``pos_list[d]`` the
    ``(B, max_b counts[d, b] * page_t)`` global position held by each
    pool slot (INT32_MAX pads never validate in the attention masks).
    The two-device case reproduces :func:`_kv_layout_rows` exactly."""
    assign = np.asarray(assign)
    B, P = assign.shape
    local = np.zeros((B, P), np.int32)
    counts = np.zeros((n_devices, B), np.int64)
    pos_list = []
    at = np.arange(page_t)
    for d in range(n_devices):
        mask = assign == d
        counts[d] = mask.sum(axis=1)
        local = np.where(mask, np.cumsum(mask, axis=1) - 1, local).astype(
            np.int32)
        need = int(counts[d].max(initial=0))
        if need == 0:
            pos_list.append(np.zeros((B, 0), np.int32))
            continue
        # pages of d first (stable keeps page order), then the rest
        order = np.argsort(~mask, axis=1, kind="stable")[:, :need]
        allpos = (order[:, :, None] * page_t + at).reshape(
            B, need * page_t).astype(np.int32)
        cols = np.arange(need * page_t)
        pos_d = np.where(cols[None, :] < counts[d][:, None] * page_t,
                         allpos, _INT32_MAX)
        pos_list.append(pos_d.astype(np.int32))
    return local, counts, pos_list


def _pad_pos(pos: np.ndarray, T: int) -> np.ndarray:
    """Pad a (B, t) position map to (B, T) with never-valid sentinels."""
    if pos.shape[1] >= T:
        return pos
    pad = np.full((pos.shape[0], T - pos.shape[1]), _INT32_MAX, np.int32)
    return np.concatenate([pos, pad], axis=1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TieredKVCache:
    #: per-device K/V pools: ``k_parts[d]`` is ``(L, B, T_d, K, hd)``.
    #: ``T_0 = n_pages * page_t`` (the fast home tier never reallocates);
    #: slow pools hold their own pages plus ``slow_headroom`` pages.
    k_parts: tuple
    v_parts: tuple
    lengths: jax.Array  # (B,)
    # static addressing (per-slot page assignment)
    page_local: jax.Array  # (B, n_pages): page slot within its OWN device pool
    #: per-device (B, T_d) global position held by each pool slot.
    pos_parts: tuple
    #: per-page owning DEVICE ordinal (0 = fast, i >= 1 = slow device i-1);
    #: storage AND accounting are per device (ISSUE 7).
    page_device: jax.Array  # (B, n_pages) int8
    page_t: int
    #: route labels per device ordinal (telemetry/mover tier names).
    device_names: tuple = ("fast", "slow")
    #: slow-pool capacity padding, in pages per slot per device.  0 =
    #: each slow pool is sized exactly for its current worst slot (every
    #: retile that changes that resizes it — the legacy layout); > 0 =
    #: each slow pool keeps ``max_count + slow_headroom`` pages of
    #: capacity, so Caption repartitions and SLO pins that fit take the
    #: O(Δ) stable path and never change the decode step's shapes (zero
    #: retraces across probe epochs).
    slow_headroom: int = 0
    #: shared-prefix page pool (ISSUE 8) — ``None`` disables sharing and
    #: keeps the legacy treedef.  When set, decode attends one extra
    #: partition of referenced pool pages; a slot's own pool rows below
    #: its ``slot_shared`` boundary are pos-sentineled out of attention
    #: (the reference serves those positions instead).
    prefix: Optional[PrefixBlock] = None

    def tree_flatten(self):
        children = (tuple(self.k_parts), tuple(self.v_parts), self.lengths,
                    self.page_local, tuple(self.pos_parts), self.page_device,
                    self.prefix)
        return children, (self.page_t, self.device_names,
                          self.slow_headroom)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (k_parts, v_parts, lengths, page_local, pos_parts, page_device,
         prefix) = children
        return cls(tuple(k_parts), tuple(v_parts), lengths, page_local,
                   tuple(pos_parts), page_device, page_t=aux[0],
                   device_names=aux[1], slow_headroom=aux[2], prefix=prefix)

    # -- two-pool compatibility views ------------------------------------------
    @property
    def k_fast(self) -> jax.Array:
        return self.k_parts[0]

    @property
    def v_fast(self) -> jax.Array:
        return self.v_parts[0]

    @property
    def pos_fast(self) -> jax.Array:
        return self.pos_parts[0]

    @property
    def k_slow(self) -> jax.Array:
        """The FIRST slow device's pool (two-device compatibility view;
        on wider topologies index ``.k_parts`` directly)."""
        return self.k_parts[1]

    @property
    def v_slow(self) -> jax.Array:
        return self.v_parts[1]

    @property
    def pos_slow(self) -> jax.Array:
        return self.pos_parts[1]

    @property
    def page_tier(self) -> jax.Array:
        """(B, n_pages) int8 0/1 fast-vs-slow view of the device map."""
        return jnp.minimum(self.page_device, 1).astype(jnp.int8)

    # -- host-side map cache ----------------------------------------------------
    def _host_dev(self) -> np.ndarray:
        """Cached numpy page->device map: the Caption loop reads
        ``slow_fraction``/``weights`` every epoch and must not re-sync
        the device array each time."""
        cached = self.__dict__.get("_host_cache")
        if cached is None:
            cached = np.asarray(self.page_device)
            self.__dict__["_host_cache"] = cached
        return cached

    # -- construction ---------------------------------------------------------
    @classmethod
    def create(cls, cfg: ArchConfig, batch: int, max_len: int,
               policy: MemPolicy, *, page_t: int = 256, dtype=None,
               slow_headroom: int = 0) -> "TieredKVCache":
        dt = dtype or dtype_of(cfg.param_dtype)
        L, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
        page_t = min(page_t, max_len)
        assert max_len % page_t == 0
        n_pages = max_len // page_t
        slow_headroom = min(max(int(slow_headroom), 0), n_pages)
        dev_row, names = _policy_device_map(policy, n_pages)
        dev = np.broadcast_to(dev_row.astype(np.int8), (batch, n_pages))
        n_devices = len(names)
        local, counts, pos_list = _kv_device_layout_rows(dev, page_t,
                                                         n_devices)
        caps = [n_pages * page_t]  # fast pool holds every page
        for d in range(1, n_devices):
            caps.append(min(int(counts[d].max(initial=0)) + slow_headroom,
                            n_pages) * page_t)
        out = cls(
            k_parts=tuple(jnp.zeros((L, batch, caps[d], K, hd), dt)
                          for d in range(n_devices)),
            v_parts=tuple(jnp.zeros((L, batch, caps[d], K, hd), dt)
                          for d in range(n_devices)),
            lengths=jnp.zeros((batch,), jnp.int32),
            page_local=jnp.asarray(local, jnp.int32),
            pos_parts=tuple(jnp.asarray(_pad_pos(pos_list[d], caps[d]))
                            for d in range(n_devices)),
            page_device=jnp.asarray(dev, jnp.int8),
            page_t=page_t,
            device_names=names,
            slow_headroom=slow_headroom,
        )
        out.__dict__["_host_cache"] = np.asarray(dev)
        return out

    # -- addressing -------------------------------------------------------------
    def _route(self, pos: jax.Array):
        """token position -> (owning device ordinal, flat pool row)."""
        page = pos // self.page_t
        page = jnp.minimum(page, self.page_device.shape[1] - 1)[:, None]
        dev = jnp.take_along_axis(self.page_device, page, axis=1)[:, 0]
        local = jnp.take_along_axis(self.page_local, page, axis=1)[:, 0]
        return dev, local * self.page_t + pos % self.page_t

    def slow_fraction(self, pinned_slots=()) -> float:
        """Slow-page share of the *tunable* slots (all slots minus
        ``pinned_slots``) — the operating point the Caption actuation
        feedback must report.  Pin state lives with the engine (request
        SLO policy), not in this data structure."""
        tiers = np.minimum(self._host_dev(), 1).astype(np.float32)
        pinned = set(pinned_slots)
        unpinned = [b for b in range(tiers.shape[0]) if b not in pinned]
        if not unpinned:
            return 0.0
        return float(tiers[unpinned].mean())

    def weights(self, pinned_slots=()) -> tuple[float, ...]:
        """Per-slow-device page shares of the tunable slots (the Caption
        weight-vector operating point on an N-device topology)."""
        dev = self._host_dev()
        pinned = set(pinned_slots)
        unpinned = [b for b in range(dev.shape[0]) if b not in pinned]
        n_slow = max(len(self.device_names) - 1, 1)
        if not unpinned:
            return (0.0,) * n_slow
        sub = dev[unpinned]
        return tuple(float((sub == i + 1).mean()) for i in range(n_slow))

    def device_fractions(self, pinned_slots=()) -> dict[str, float]:
        """Per-device page share of the tunable slots, keyed by name."""
        w = self.weights(pinned_slots)
        out = {self.device_names[0]: 1.0 - sum(w)}
        for i, share in enumerate(w):
            out[self.device_names[i + 1]] = share
        return out

    # -- per-step traffic (drives the latency/QPS simulation) ------------------
    def read_bytes_per_step(self) -> dict[str, int]:
        """Bytes streamed per decode step per tier (both K and V), from the
        per-slot page placement (pinned slots bill fast-only)."""
        item = self.k_parts[0].dtype.itemsize
        L = self.k_parts[0].shape[0]
        K, hd = self.k_parts[0].shape[3:]
        tiers = np.minimum(self._host_dev(), 1)
        n_pages = tiers.shape[1]
        slow_pages = tiers.sum(axis=1)
        fast_rows = int(np.maximum((n_pages - slow_pages), 1).sum()) * self.page_t
        slow_rows = int(slow_pages.sum()) * self.page_t
        out = {
            "fast": 2 * L * fast_rows * K * hd * item,
            "slow": 2 * L * slow_rows * K * hd * item,
        }
        # Prefix references READ per referencing slot (every reader
        # streams the rows), unlike storage/migration, billed once.
        for dev_ord, n_refs in self._prefix_ref_pages().items():
            key = "fast" if dev_ord == 0 else "slow"
            out[key] += n_refs * self._page_kv_bytes()
        return out

    def read_bytes_per_device(self) -> dict[str, int]:
        """Per-device decode-step read bytes, keyed by device name — the
        slow total splits across the real devices holding the pages (each
        device streams on its own link, so the modeled step time is the
        max, not the sum)."""
        item = self.k_parts[0].dtype.itemsize
        L = self.k_parts[0].shape[0]
        K, hd = self.k_parts[0].shape[3:]
        dev = self._host_dev()
        out = {}
        ref_pages = self._prefix_ref_pages()
        for i, name in enumerate(self.device_names):
            pages = (dev == i).sum(axis=1)
            if i == 0:
                pages = np.maximum(pages, 1)  # >= 1 fast page per slot
            out[name] = (2 * L * int(pages.sum()) * self.page_t * K * hd
                         * item
                         + ref_pages.get(i, 0) * self._page_kv_bytes())
        return out

    def storage_bytes_per_device(self) -> dict[str, int]:
        """Physically OCCUPIED bytes per device pool (valid page slots,
        K and V, all layers), read off the pos maps' sentinel structure.
        With per-device pools this equals the ``read_bytes_per_device``
        accounting (modulo the fast tier's >= 1-page billing floor) —
        the ISSUE 7 storage == accounting invariant."""
        item = self.k_parts[0].dtype.itemsize
        L = self.k_parts[0].shape[0]
        K, hd = self.k_parts[0].shape[3:]
        out = {}
        pfx_dev = (np.asarray(self.prefix.page_device)
                   if self.prefix is not None else None)
        for i, name in enumerate(self.device_names):
            rows = int((np.asarray(self.pos_parts[i]) != _INT32_MAX).sum())
            out[name] = 2 * L * rows * K * hd * item
            if pfx_dev is not None:
                # shared pool pages occupy storage ONCE, however many
                # slots reference them — the dedup Caption observes.
                out[name] += (int((pfx_dev == i).sum())
                              * self._page_kv_bytes())
        return out

    def pool_bytes_per_device(self) -> dict[str, int]:
        """ALLOCATED pool capacity per device, keyed by device name —
        what the :class:`~repro.core.ledger.TierLedger` should bill.

        Unlike :meth:`storage_bytes_per_device` (occupied page slots),
        this is the framework-RESERVED backing: the full K/V/pos pool
        arrays per device, plus the shared-prefix pool's pages billed to
        the device their label names.  Unallocated prefix pool slack is
        billed to the fast tier (the pool is materialized as one buffer
        and free slots have not been pushed over a CXL link yet)."""
        out = {}
        for i, name in enumerate(self.device_names):
            out[name] = int(
                (self.k_parts[i].size + self.v_parts[i].size)
                * self.k_parts[i].dtype.itemsize
                + self.pos_parts[i].size * self.pos_parts[i].dtype.itemsize)
        if self.prefix is not None:
            pdev = np.asarray(self.prefix.page_device)
            pb = self.prefix.page_bytes()
            for i, name in enumerate(self.device_names):
                out[name] += int((pdev == i).sum()) * pb
            out[self.device_names[0]] += (
                int((pdev == UNALLOCATED).sum()) * pb)
        return out

    def register_in_ledger(self, ledger, buffer: str = "kv_cache", *,
                           device_names=None, note: str = "serving KV pool",
                           strict: bool = False) -> dict[str, int]:
        """Register (or refresh) this cache's pools in a
        :class:`~repro.core.ledger.TierLedger` so ``report()`` covers
        the serving plane's framework-managed bytes.

        ``device_names`` maps this cache's device ordinals onto the
        ledger topology's tier names when the cache was built with the
        generic ``("fast", "slow")`` labels.  Re-registering under the
        same ``buffer`` releases the previous entries first, so epoch
        refreshes after a re-tile never double-bill."""
        names = tuple(device_names) if device_names else self.device_names
        if len(names) != len(self.device_names):
            raise ValueError(
                f"{len(names)} names for {len(self.device_names)} devices")
        pool = self.pool_bytes_per_device()
        ledger.release(buffer)
        billed = {}
        for cache_name, ledger_name in zip(self.device_names, names):
            nbytes = pool[cache_name]
            if not nbytes:
                continue
            try:
                ledger.register(buffer, ledger_name, nbytes, note,
                                strict=strict)
            except KeyError:
                # device outside the ledger topology (e.g. elastically
                # removed): its residual backing has no tier to bill
                continue
            billed[ledger_name] = nbytes
        return billed

    def _prefix_ref_pages(self) -> dict[int, int]:
        """Per-device ordinal count of prefix-page REFERENCES held by
        slots (a page referenced by r slots counts r times — every
        reader streams it each decode step)."""
        if self.prefix is None:
            return {}
        sp = np.asarray(self.prefix.slot_pages)
        pdev = np.asarray(self.prefix.page_device)
        refs = sp[sp >= 0]
        if refs.size == 0:
            return {}
        devs = pdev[refs]
        return {int(d): int((devs == d).sum()) for d in np.unique(devs)
                if d >= 0}

    def capacity_pages(self) -> tuple:
        """Per-device pool capacity in pages per slot."""
        return tuple(kp.shape[2] // self.page_t for kp in self.k_parts)

    # -- append + attend --------------------------------------------------------
    def append_layer(self, layer: jax.Array, k_new: jax.Array, v_new: jax.Array):
        """Scatter one token's K/V for one layer. k_new: (B, K, hd)."""
        B = k_new.shape[0]
        dev, local = self._route(self.lengths)
        bidx = jnp.arange(B)
        k_parts = list(self.k_parts)
        v_parts = list(self.v_parts)
        for d in range(len(k_parts)):
            T_d = k_parts[d].shape[2]
            if T_d == 0:
                continue
            # rows owned by another device are pushed out of bounds and
            # dropped — every pool sees one shape-static scatter.
            idx = jnp.where(dev == d, local, T_d)
            k_parts[d] = k_parts[d].at[layer, bidx, idx].set(
                k_new.astype(k_parts[d].dtype), mode="drop")
            v_parts[d] = v_parts[d].at[layer, bidx, idx].set(
                v_new.astype(v_parts[d].dtype), mode="drop")
        return dataclasses.replace(
            self, k_parts=tuple(k_parts), v_parts=tuple(v_parts))

    # -- SLO pinning (per-request latency class) --------------------------------
    def pin_slot(self, i: int, **kwargs) -> "TieredKVCache":
        """Move slot ``i``'s pages all-fast (latency-SLO admission) on the
        mover's latency lane.  The *exclusion* from future repartitions is
        the engine's job: it tracks the pinned-slot set (request policy)
        and passes it as ``pinned_slots`` — keeping SLO state out of this
        data structure keeps the jitted decode treedef stable."""
        new_dev = self._host_dev().copy()
        new_dev[i] = 0
        return self._retile(new_dev, lane=LANE_LATENCY, **kwargs)

    # -- dynamic re-tiering (Caption actuation path) ----------------------------
    def repartition(self, policy: MemPolicy, pinned_slots=(), **kwargs
                    ) -> "TieredKVCache":
        """Re-tier every unpinned slot's KV pages under ``policy``, moving
        only delta pages.

        Host-side (between decode steps).  Pages whose device is unchanged
        are sliced across; changed pages ship through the BulkMover (or
        are accounted to telemetry) on their real ``(src_device,
        dst_device)`` route, so inter-tier traffic is exactly
        ``delta_pages * page_kv_bytes``.  Attention output is invariant:
        the same (position, K, V) triples exist after the move, only
        their owning device changes.  Slots in ``pinned_slots``
        (latency-SLO) keep their all-fast rows.
        """
        n_pages = self.page_device.shape[1]
        row, names = _policy_device_map(policy, n_pages)
        pinned = set(pinned_slots)
        new_dev = self._host_dev().copy()
        for b in range(new_dev.shape[0]):
            if b not in pinned:
                new_dev[b] = row
        return self._retile(new_dev, policy_names=names, **kwargs)

    def repartition_fraction(self, fraction: float, pinned_slots=(),
                             **kwargs) -> "TieredKVCache":
        """Re-tier unpinned slots to ``fraction`` slow flipping the fewest
        KV pages per slot (two-device path)."""
        return self.repartition_weights((float(fraction),), pinned_slots,
                                        **kwargs)

    def repartition_weights(self, weights, pinned_slots=(), **kwargs
                            ) -> "TieredKVCache":
        """Re-tier unpinned slots to a per-slow-device weight vector,
        flipping the fewest KV pages per slot.  A vector that rounds to
        every slot's current per-device counts is a true no-op (``self``
        returned, no mover work enqueued)."""
        pinned = set(pinned_slots)
        n_devices = max(len(self.device_names), len(tuple(weights)) + 1)
        new_dev = self._host_dev().copy()
        changed = False
        for b in range(new_dev.shape[0]):
            if b in pinned:
                continue
            row = minimal_delta_weights(new_dev[b], tuple(weights),
                                        n_devices)
            if row is not None:
                new_dev[b] = row
                changed = True
        if not changed:
            return self
        return self._retile(new_dev, **kwargs)

    def drain_device(self, device, pinned_slots=(), *, weights=None,
                     **kwargs) -> "TieredKVCache":
        """Move every unpinned slot's pages off one slow device (elastic
        hot-remove drain).

        ``device`` is a slow-device ordinal (>= 1) or its name.  The
        departing share goes to the surviving slow devices proportionally
        to their current shares by default, or to an explicit per-device
        ``weights`` target (which must zero the departing device).  The
        move rides the normal minimal-delta repartition: run-coalesced
        LANE_BULK descriptors on real (dead device -> survivor) routes,
        so in-flight requests keep decoding — only page ownership moves.
        Pinned (latency-SLO) slots are already all-fast and untouched."""
        if isinstance(device, str):
            if device not in self.device_names:
                raise KeyError(device)
            i = self.device_names.index(device)
        else:
            i = int(device)
        if not 1 <= i < len(self.device_names):
            raise KeyError(device)
        if weights is None:
            cur = list(self.weights(pinned_slots))
            departing, cur[i - 1] = cur[i - 1], 0.0
            rest = sum(cur)
            if departing > 0 and rest > 0:
                cur = [w + departing * w / rest for w in cur]
            weights = tuple(cur)
        elif weights[i - 1] > 0:
            raise ValueError(
                f"drain target keeps weight on {self.device_names[i]!r}")
        return self.repartition_weights(weights, pinned_slots, **kwargs)

    def _route_names(self, n_devices: int,
                     policy_names: Optional[tuple] = None,
                     fast_tier: Optional[str] = None,
                     slow_tier: Optional[str] = None) -> tuple:
        return resolve_device_names(self.device_names, n_devices,
                                    policy_names, fast_tier, slow_tier)

    # -- retile internals -------------------------------------------------------
    def _page_kv_bytes(self) -> int:
        L = self.k_parts[0].shape[0]
        K, hd = self.k_parts[0].shape[3:]
        return 2 * L * self.page_t * K * hd * self.k_parts[0].dtype.itemsize

    def _slot_groups(self, old_dev, new_dev, old_local) -> dict:
        """Slots sharing (old row, new row, old locals) — the whole
        batch-class population after a repartition — move as ONE batched
        slice per run instead of per-slot-per-page.  The locals are part
        of the key because the stable path's free-slot allocation makes
        them history-dependent (equal device rows no longer imply equal
        physical layouts)."""
        groups: dict = {}
        for b in range(old_dev.shape[0]):
            key = (old_dev[b].tobytes() + new_dev[b].tobytes()
                   + old_local[b].tobytes())
            groups.setdefault(key, []).append(b)
        return groups

    def _ship_retile(self, groups, old_dev, new_dev, old_local, route, *,
                     mover, telemetry, source, lane, wait=True) -> None:
        """Movement metering on real device routes — including
        slow->slow hops (the paper's C2C class).  Moved pages coalesce
        into route-pure runs of consecutive source locals; each run is
        one contiguous slab of its source pool and ships as ONE batched
        descriptor (billed bytes identical to per-page).  Runs before
        any pool is written, so payloads slice pristine source data."""
        pt = self.page_t
        page_kv_bytes = self._page_kv_bytes()
        k_np = [np.asarray(kp) for kp in self.k_parts]
        v_np = [np.asarray(vp) for vp in self.v_parts]
        descs = []
        for slots in groups.values():
            b0, sl = slots[0], np.asarray(slots)
            od, nd = old_dev[b0].astype(np.int64), new_dev[b0].astype(np.int64)
            ol = old_local[b0].astype(np.int64)
            moved = np.nonzero(od != nd)[0]
            if moved.size == 0:
                continue
            order, starts, ends = route_pure_runs(
                od[moved], nd[moved], ol[moved])
            mv = moved[order]
            for s, e in zip(starts, ends):
                p0 = mv[s]
                d0, d1 = int(od[p0]), int(nd[p0])
                l0, run = int(ol[p0]), int(e - s)
                src, dst = route[d0], route[d1]
                if mover is not None:
                    from repro.core.mover import Descriptor
                    k_slab = k_np[d0][:, sl, l0 * pt:(l0 + run) * pt]
                    v_slab = v_np[d0][:, sl, l0 * pt:(l0 + run) * pt]
                    descs.append(Descriptor(
                        src, dst, (jnp.asarray(k_slab),
                                   jnp.asarray(v_slab)),
                        lane=lane, source=source))
                elif telemetry is not None:
                    telemetry.record_move(
                        src, dst, page_kv_bytes * len(slots) * run,
                        0.0, source=source)
        if mover is not None:
            # One submission: descriptors batch (§6).  ``wait=False`` is
            # the overlap path — descriptor payloads are fancy-indexed
            # copies, so the drain pool can stream them while the caller
            # keeps decoding; the engine drains completions at the next
            # epoch boundary and accounts hidden vs exposed time.
            mover.submit(descs)
            if wait and mover.asynchronous:
                mover.wait_all()

    def _retile(self, new_dev: np.ndarray, *, mover=None,
                fast_tier: Optional[str] = None,
                slow_tier: Optional[str] = None,
                policy_names: Optional[tuple] = None,
                telemetry=GLOBAL_TELEMETRY, source: Optional[str] = None,
                lane: int = LANE_BULK, donate: bool = False,
                wait: bool = True) -> "TieredKVCache":
        old_dev = self._host_dev()
        if np.array_equal(new_dev, old_dev):
            return self
        n_old = len(self.k_parts)
        n_devices = max(len(self.device_names),
                        int(new_dev.max(initial=0)) + 1,
                        len(policy_names or ()), n_old)
        route = self._route_names(n_devices, policy_names, fast_tier,
                                  slow_tier)
        old_local = np.asarray(self.page_local)
        groups = self._slot_groups(old_dev, new_dev, old_local)
        # Bill / ship the movement FIRST (payloads slice the CURRENT
        # pools — required for the donated in-place path too).
        self._ship_retile(groups, old_dev, new_dev, old_local, route,
                          mover=mover, telemetry=telemetry, source=source,
                          lane=lane, wait=wait)
        caps = self.capacity_pages()
        need = [int(max((new_dev == d).sum(axis=1).max(initial=0), 0))
                for d in range(n_devices)]
        stable = (self.slow_headroom > 0 and n_devices == n_old
                  and all(need[d] <= caps[d] for d in range(n_devices)))
        if stable:
            out = self._retile_stable(groups, old_dev, new_dev, old_local,
                                      donate=donate)
        else:
            out = self._retile_rebuild(groups, old_dev, new_dev, old_local,
                                       n_devices)
        # Stored names: the policy's, widened with the cache's EXISTING
        # names for higher ordinals (a narrower policy must not rename a
        # pinned slot's real device to a placeholder), without the legacy
        # fast/slow route overrides.
        out = dataclasses.replace(
            out, device_names=self._route_names(n_devices, policy_names,
                                                None, None))
        # Both retile paths recompute moved slots' pos rows from the page
        # layout, which revives own-pool rows a prefix reference serves —
        # re-sentinel everything below each slot's shared boundary.
        if out.prefix is not None:
            out = out._apply_prefix_sentinels()
        out.__dict__["_host_cache"] = np.asarray(new_dev)
        return out

    def _apply_prefix_sentinels(self) -> "TieredKVCache":
        shared = self.prefix.slot_shared[:, None]
        pos_new = tuple(jnp.where(p < shared, _INT32_MAX, p)
                        for p in self.pos_parts)
        return dataclasses.replace(self, pos_parts=pos_new)

    def _retile_stable(self, groups, old_dev, new_dev, old_local, *,
                       donate: bool = False) -> "TieredKVCache":
        """O(Δ) retile: every moved page lands in a free slot of its
        destination pool — pool shapes, the treedef, and every unmoved
        page's slot are untouched, so the jitted decode step keeps its
        trace.  Non-receiving pools are reused as-is; receiving pools
        are either copy-on-write (one full copy each) or — with
        ``donate`` — patched in place through the jitted donated scatter
        (zero full-pool copies; the caller must drop the parent cache).

        ORDERING HAZARD: a leaving page's old slot counts as free in its
        pool, so writes could clobber data another destination has not
        staged yet — every moved slab is gathered FIRST, then written."""
        pt = self.page_t
        at = np.arange(pt)
        L_idx = np.arange(self.k_parts[0].shape[0])
        caps = self.capacity_pages()
        n_devices = len(self.k_parts)
        new_local = old_local.copy()
        k_np = [np.asarray(kp) for kp in self.k_parts]   # pristine views
        v_np = [np.asarray(vp) for vp in self.v_parts]
        pos_np = [np.asarray(p).copy() for p in self.pos_parts]
        plan = []  # (dst_dev, slot ids, dst rows, k slab, v slab)
        for slots in groups.values():
            b0, sl = slots[0], np.asarray(slots)
            od, nd = old_dev[b0].astype(np.int64), new_dev[b0].astype(np.int64)
            ol = old_local[b0].astype(np.int64)
            moved = np.nonzero(od != nd)[0]
            if moved.size == 0:
                continue
            nl_row = ol.copy()
            for d in np.unique(nd[moved]):
                incoming = moved[nd[moved] == d]
                # free slots = capacity minus the slots kept by staying
                # pages (a leaving page's slot IS free — hence the
                # gather-first discipline)
                staying = (od == d) & (nd == d)
                used = np.zeros(caps[int(d)], bool)
                used[ol[staying]] = True
                free = np.nonzero(~used)[0]
                slots_free = free[: incoming.size]
                nl_row[incoming] = slots_free
                # stage the moved slabs per source pool, aligned with
                # their destination slots
                src_of = od[incoming]
                for s in np.unique(src_of):
                    sel = src_of == s
                    pages = incoming[sel]
                    src_rows = (ol[pages][:, None] * pt + at).ravel()
                    dst_rows = (slots_free[sel][:, None] * pt + at).ravel()
                    plan.append((int(d), sl, dst_rows,
                                 k_np[int(s)][np.ix_(L_idx, sl, src_rows)],
                                 v_np[int(s)][np.ix_(L_idx, sl, src_rows)]))
            new_local[np.ix_(sl, np.arange(nl_row.size))] = \
                nl_row[None, :].astype(np.int32)
            # recompute the group's pos rows for every device (cheap:
            # O(P) per group, pool widths unchanged)
            for d in range(n_devices):
                row = np.full(caps[d] * pt, _INT32_MAX, np.int32)
                pages_d = np.nonzero(nd == d)[0]
                if pages_d.size:
                    row[(nl_row[pages_d][:, None] * pt + at).ravel()] = (
                        pages_d[:, None] * pt + at).ravel().astype(np.int32)
                pos_np[d][sl] = row
        # All staging gathered (plan slabs are fancy-indexed copies) —
        # release the zero-copy host views BEFORE writing: a live view
        # blocks XLA aliasing and donation silently degrades to a full
        # copy (repro.core.donation VIEW HAZARD).
        k_np = v_np = None
        k_pools = list(self.k_parts)
        v_pools = list(self.v_parts)
        writable_k: dict = {}
        writable_v: dict = {}
        for d, sl, dst_rows, k_slab, v_slab in plan:
            if donate:
                k_pools[d] = donated_kv_update(k_pools[d], sl, dst_rows,
                                               k_slab)
                v_pools[d] = donated_kv_update(v_pools[d], sl, dst_rows,
                                               v_slab)
                continue
            if d not in writable_k:
                FULL_SHARD_COPIES.bump(2)  # one full CoW per K and V pool
                writable_k[d] = np.asarray(k_pools[d]).copy()
                writable_v[d] = np.asarray(v_pools[d]).copy()
            writable_k[d][np.ix_(L_idx, sl, dst_rows)] = k_slab
            writable_v[d][np.ix_(L_idx, sl, dst_rows)] = v_slab
        for d in writable_k:
            k_pools[d] = jnp.asarray(writable_k[d])
            v_pools[d] = jnp.asarray(writable_v[d])
        return dataclasses.replace(
            self,
            k_parts=tuple(k_pools), v_parts=tuple(v_pools),
            page_local=jnp.asarray(new_local, jnp.int32),
            pos_parts=tuple(jnp.asarray(p) for p in pos_np),
            page_device=jnp.asarray(new_dev, jnp.int8),
        )

    def _retile_rebuild(self, groups, old_dev, new_dev, old_local,
                        n_devices: int) -> "TieredKVCache":
        """Full rebuild: re-rank locals, reallocate every pool at its new
        capacity (plus headroom), and copy every page — the path that
        changes shapes, so the jitted decode retraces once, by design
        (a pool outgrew its capacity or the device set changed)."""
        pt = self.page_t
        at = np.arange(pt)
        L, B = self.k_parts[0].shape[:2]
        K, hd = self.k_parts[0].shape[3:]
        dt = self.k_parts[0].dtype
        L_idx = np.arange(L)
        P = old_dev.shape[1]
        old_caps = self.capacity_pages()
        new_local, counts, pos_list = _kv_device_layout_rows(
            new_dev, pt, n_devices)
        caps = [P]  # fast pool holds every page
        for d in range(1, n_devices):
            need = int(counts[d].max(initial=0))
            if (self.slow_headroom > 0 and d < len(old_caps)
                    and old_caps[d] >= need):
                caps.append(old_caps[d])  # held capacity: no retrace churn
            else:
                caps.append(min(need + self.slow_headroom, P))
        k_new = [np.zeros((L, B, caps[d] * pt, K, hd), dt)
                 for d in range(n_devices)]
        v_new = [np.zeros((L, B, caps[d] * pt, K, hd), dt)
                 for d in range(n_devices)]
        FULL_SHARD_COPIES.bump(2 * n_devices)
        k_np = [np.asarray(kp) for kp in self.k_parts]
        v_np = [np.asarray(vp) for vp in self.v_parts]
        n_old = len(self.k_parts)
        for slots in groups.values():
            b0, sl = slots[0], np.asarray(slots)
            od, nd = old_dev[b0].astype(np.int64), new_dev[b0].astype(np.int64)
            ol = old_local[b0].astype(np.int64)
            nl = new_local[b0].astype(np.int64)
            # one fancy-indexed copy per (source pool, dest pool) pair
            for s in range(n_old):
                sel_s = od == s
                if not sel_s.any():
                    continue
                for d in range(n_devices):
                    sel = np.nonzero(sel_s & (nd == d))[0]
                    if sel.size == 0:
                        continue
                    src_rows = (ol[sel][:, None] * pt + at).ravel()
                    dst_rows = (nl[sel][:, None] * pt + at).ravel()
                    k_new[d][np.ix_(L_idx, sl, dst_rows)] = \
                        k_np[s][np.ix_(L_idx, sl, src_rows)]
                    v_new[d][np.ix_(L_idx, sl, dst_rows)] = \
                        v_np[s][np.ix_(L_idx, sl, src_rows)]
        return dataclasses.replace(
            self,
            k_parts=tuple(jnp.asarray(k) for k in k_new),
            v_parts=tuple(jnp.asarray(v) for v in v_new),
            page_local=jnp.asarray(new_local, jnp.int32),
            pos_parts=tuple(
                jnp.asarray(_pad_pos(pos_list[d], caps[d] * pt))
                for d in range(n_devices)),
            page_device=jnp.asarray(new_dev, jnp.int8),
        )

    # -- shared-prefix pool (ISSUE 8) -------------------------------------------
    def with_prefix(self, pool_pages: int) -> "TieredKVCache":
        """Attach an (empty) shared-prefix page pool of ``pool_pages``
        pages.  Done once at engine construction: the pool is a pytree
        child, so creating it later would change the jitted decode
        treedef mid-run."""
        L, B = self.k_parts[0].shape[:2]
        K, hd = self.k_parts[0].shape[3:]
        blk = PrefixBlock.create(
            B, pool_pages, self.page_device.shape[1], self.page_t,
            L, K, hd, self.k_parts[0].dtype)
        return dataclasses.replace(self, prefix=blk)

    def attach_prefix(self, i: int, pages) -> "TieredKVCache":
        """Map shared pool pages into slot ``i`` BY REFERENCE: the slot's
        leading positions are served by the pool partition, its own pool
        rows below the boundary are sentineled out of attention (they
        hold no data — the dedup), and ``lengths`` jumps to the shared
        boundary so prefill replays only the suffix."""
        assert self.prefix is not None
        pages = [int(p) for p in pages]
        Pm = self.prefix.slot_pages.shape[1]
        assert len(pages) <= Pm
        full_rows = len(pages) * self.page_t
        row = np.full(Pm, NO_PAGE, np.int32)
        row[:len(pages)] = pages
        blk = dataclasses.replace(
            self.prefix,
            slot_pages=self.prefix.slot_pages.at[i].set(jnp.asarray(row)),
            slot_shared=self.prefix.slot_shared.at[i].set(full_rows))
        out = dataclasses.replace(
            self, prefix=blk, lengths=self.lengths.at[i].set(full_rows))
        pos_new = []
        for p in out.pos_parts:
            rowv = p[i]
            pos_new.append(p.at[i].set(
                jnp.where(rowv < full_rows, _INT32_MAX, rowv)))
        return dataclasses.replace(out, pos_parts=tuple(pos_new))

    def detach_prefix(self, i: int) -> "TieredKVCache":
        """Drop slot ``i``'s references (request finished) and restore
        its own-pool pos rows from the page layout, so the slot is
        reusable by a reference-free request."""
        if self.prefix is None:
            return self
        blk = dataclasses.replace(
            self.prefix,
            slot_pages=self.prefix.slot_pages.at[i].set(NO_PAGE),
            slot_shared=self.prefix.slot_shared.at[i].set(0))
        out = dataclasses.replace(self, prefix=blk)
        return out._restore_slot_pos(i)

    def _restore_slot_pos(self, i: int) -> "TieredKVCache":
        pt = self.page_t
        at = np.arange(pt)
        dev = self._host_dev()[i]
        loc = np.asarray(self.page_local)[i]
        pos_new = list(self.pos_parts)
        for d in range(len(self.k_parts)):
            T_d = self.k_parts[d].shape[2]
            row = np.full(T_d, _INT32_MAX, np.int32)
            pages_d = np.nonzero(dev == d)[0]
            if pages_d.size:
                row[(loc[pages_d][:, None] * pt + at).ravel()] = (
                    pages_d[:, None] * pt + at).ravel().astype(np.int32)
            pos_new[d] = pos_new[d].at[i].set(jnp.asarray(row))
        return dataclasses.replace(self, pos_parts=tuple(pos_new))

    def _slot_row_route(self, i: int, start: int, n: int):
        """(per-position device, own-pool row) for slot ``i`` positions
        ``[start, start + n)`` — host-side fancy-index plumbing for the
        CoW and promotion copies."""
        positions = np.arange(start, start + n)
        page = positions // self.page_t
        dev = self._host_dev()[i][page]
        rows = (np.asarray(self.page_local)[i][page] * self.page_t
                + positions % self.page_t)
        return dev, rows

    def gather_token_rows(self, i: int, start: int, n: int):
        """Copy slot ``i``'s own K/V rows for positions ``[start,
        start + n)`` out of the per-device pools: ``(L, n, K, hd)``
        numpy pair (promotion of freshly-prefilled pages into the shared
        pool)."""
        L = self.k_parts[0].shape[0]
        K, hd = self.k_parts[0].shape[3:]
        dev, rows = self._slot_row_route(i, start, n)
        out_k = np.zeros((L, n, K, hd), self.k_parts[0].dtype)
        out_v = np.zeros_like(out_k)
        for d in np.unique(dev):
            sel = np.nonzero(dev == d)[0]
            out_k[:, sel] = np.asarray(self.k_parts[d])[:, i, rows[sel]]
            out_v[:, sel] = np.asarray(self.v_parts[d])[:, i, rows[sel]]
        return out_k, out_v

    def write_token_rows(self, i: int, start: int, k_rows,
                         v_rows) -> "TieredKVCache":
        """Write ``(L, n, K, hd)`` rows into slot ``i``'s OWN pools at
        positions ``[start, ...)`` — the copy-on-write landing: a
        diverging request's private copy goes into whatever tier its
        own pages occupy."""
        n = k_rows.shape[1]
        dev, rows = self._slot_row_route(i, start, n)
        k_parts = list(self.k_parts)
        v_parts = list(self.v_parts)
        for d in np.unique(dev):
            sel = np.nonzero(dev == d)[0]
            idx = jnp.asarray(rows[sel])
            k_parts[d] = k_parts[d].at[:, i, idx].set(
                jnp.asarray(k_rows[:, sel], k_parts[d].dtype))
            v_parts[d] = v_parts[d].at[:, i, idx].set(
                jnp.asarray(v_rows[:, sel], v_parts[d].dtype))
        return dataclasses.replace(
            self, k_parts=tuple(k_parts), v_parts=tuple(v_parts),
            lengths=self.lengths.at[i].set(start + n))

    def write_prefix_pages(self, pool_slots, k_pages, v_pages, *,
                           device: int = 0) -> "TieredKVCache":
        """Fill shared pool pages (promotion): ``k_pages`` is
        ``(L, n, page_t, K, hd)`` for ``n`` pool slots, landing on
        device ordinal ``device`` (new prefixes are born fast; the
        epoch-level prefix retier demotes cold ones)."""
        assert self.prefix is not None
        idx = jnp.asarray(np.asarray(pool_slots, np.int32))
        blk = dataclasses.replace(
            self.prefix,
            k=self.prefix.k.at[:, idx].set(
                jnp.asarray(k_pages, self.prefix.k.dtype)),
            v=self.prefix.v.at[:, idx].set(
                jnp.asarray(v_pages, self.prefix.v.dtype)),
            page_device=self.prefix.page_device.at[idx].set(device))
        return dataclasses.replace(self, prefix=blk)

    def retile_prefix(self, new_dev, *, mover=None,
                      telemetry=GLOBAL_TELEMETRY,
                      policy_names: Optional[tuple] = None,
                      source: Optional[str] = None, lane: int = LANE_BULK,
                      wait: bool = True) -> "TieredKVCache":
        """Re-tier the shared pool's per-page placement.  Each moved page
        bills its bytes ONCE on its real route however many slots
        reference it — refcount-weighted (deduplicated) migration, vs
        the per-slot billing private pages pay in ``_retile``."""
        assert self.prefix is not None
        old = np.asarray(self.prefix.page_device)
        new_dev = np.asarray(new_dev, np.int32)
        moved = np.nonzero((old >= 0) & (new_dev >= 0)
                           & (old != new_dev))[0]
        if moved.size == 0:
            return self
        n_devices = max(len(self.device_names), int(new_dev.max()) + 1)
        route = self._route_names(n_devices, policy_names, None, None)
        page_b = self._page_kv_bytes()
        routes: dict[tuple, list] = {}
        for pg in moved:
            routes.setdefault((int(old[pg]), int(new_dev[pg])),
                              []).append(int(pg))
        if mover is not None:
            from repro.core.mover import Descriptor
            k_np = np.asarray(self.prefix.k)
            v_np = np.asarray(self.prefix.v)
            descs = [Descriptor(route[d0], route[d1],
                                (jnp.asarray(k_np[:, pages]),
                                 jnp.asarray(v_np[:, pages])),
                                lane=lane, source=source)
                     for (d0, d1), pages in routes.items()]
            mover.submit(descs)
            if wait and mover.asynchronous:
                mover.wait_all()
        elif telemetry is not None:
            for (d0, d1), pages in routes.items():
                telemetry.record_move(route[d0], route[d1],
                                      page_b * len(pages), 0.0,
                                      source=source)
        out = old.copy()
        out[moved] = new_dev[moved]
        blk = dataclasses.replace(self.prefix,
                                  page_device=jnp.asarray(out))
        return dataclasses.replace(self, prefix=blk)

    def ordered(self, layer: int):
        """``(k, v, valid)`` of one layer for decode attention
        (post-append), every slot's rows in position order: ``(B,
        n_pages * page_t, K, hd)``.  Each row is read from the device pool
        that holds its page, or from the shared-prefix pool below the
        slot's shared boundary.  Attention over this view reduces in one
        order whatever the placement, so tiering never changes an answer
        (a log-sum-exp merge of per-pool partials rounds differently for
        each placement, and bf16 matmuls make that visible in tokens)."""
        upto = self.lengths[:, None] + 1
        T = self.page_device.shape[1] * self.page_t
        ks, vs, pos = [], [], []
        for d in range(len(self.k_parts)):
            if self.k_parts[d].shape[2]:
                ks.append(self.k_parts[d][layer])
                vs.append(self.v_parts[d][layer])
                pos.append(jnp.where(self.pos_parts[d] < upto,
                                     self.pos_parts[d], T))
        if self.prefix is not None and self.prefix.pool_pages:
            k, v, valid = self.prefix.partition(layer)
            ks.append(k)
            vs.append(v)
            pos.append(jnp.where(valid, jnp.arange(valid.shape[1]), T))
        pos = jnp.concatenate(pos, axis=1)
        B, R = pos.shape
        bidx = jnp.arange(B)[:, None]
        src = jnp.zeros((B, T), jnp.int32).at[bidx, pos].set(
            jnp.arange(R, dtype=jnp.int32), mode="drop")
        valid = jnp.zeros((B, T), bool).at[bidx, pos].set(True, mode="drop")

        def gather(parts):
            return jnp.take_along_axis(jnp.concatenate(parts, axis=1),
                                       src[:, :, None, None], axis=1)

        return gather(ks), gather(vs), valid


def tiered_decode_step(cfg: ArchConfig, params: dict, cache: TieredKVCache,
                       tokens: jax.Array) -> tuple[jax.Array, TieredKVCache]:
    """One decode step for the dense family over a tiered KV cache."""
    B = tokens.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    x = jnp.take(params["embed"], tokens, axis=0)
    pos = cache.lengths

    for li in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        h = apply_norm(x[:, None], lp["ln1"], cfg.norm)[:, 0]
        q = h @ lp["attn"]["wq"]
        k = h @ lp["attn"]["wk"]
        v = h @ lp["attn"]["wv"]
        if "bq" in lp["attn"]:
            q, k, v = (q + lp["attn"]["bq"], k + lp["attn"]["bk"],
                       v + lp["attn"]["bv"])
        q = q.reshape(B, H, hd)
        k = k.reshape(B, K, hd)
        v = v.reshape(B, K, hd)
        if cfg.rope:
            from repro.models.common import apply_rope
            q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta, cfg.rope_pct)[:, 0]
            k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta, cfg.rope_pct)[:, 0]
        cache = cache.append_layer(li, k, v)
        o = attn.merge_partials([attn.attend_partial(q, *cache.ordered(li))]
                                ).astype(x.dtype)
        x = x + o.reshape(B, H * hd) @ lp["attn"]["wo"]
        h = apply_norm(x[:, None], lp["ln2"], cfg.norm)[:, 0]
        x = x + mlp_apply(h, lp["mlp"], cfg.act)

    x = apply_norm(x[:, None], params["final_norm"], cfg.norm)[:, 0]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return logits, dataclasses.replace(cache, lengths=cache.lengths + 1)

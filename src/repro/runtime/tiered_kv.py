"""Device-resident tiered KV page store — the paper's near/far split, executed.

Before this module the serving engine only *accounted* the near/far tier
split host-side (core/placement keeps a tier byte per page) while the
decode math read one flat KV buffer. Here the split is real device state:

  * ``near``  — (near_capacity, D) f32/bf16 rows, the small high-bandwidth
    "HBM" tier that captures most of the bandwidth because few pages are hot;
  * ``far_q`` + ``far_scale`` — (n_pages, D) int8 rows with per-row scales,
    the capacity tier (every page has a reserved far slot, so demotion never
    allocates);
  * ``tier`` / ``slot`` — device int32 maps consumed by the fused Pallas
    kernel (kernels/tiered_gather): tier bit selects the store, slot the row.

Reads go through :meth:`lookup_segments` → ONE fused ragged kernel pass per
engine step (near gather + far gather with dequant + per-segment near/far
hit counting), with the counts accumulated into a device-resident counter
plane (per-slot, per-tenant-index, and total accumulators) instead of
synced to host ints. :meth:`drain_counters` is the only host sync: it
materializes and zeroes the plane, and the serving engine calls it once
per profiler window — the books it charges are bit-identical to charging
every call, because the plane is a pure sum. :meth:`lookup` keeps the
legacy per-call signature (counters returned as host ints, one sync per
call) for direct callers and the dispatch-budget benchmark's baseline.
Placement pushes go through :meth:`migrate` → real data movement:
promotions dequantize far rows into freed near slots, demotions quantize
near rows back into their far slots. ``flat`` mirrors every write into the legacy flat f32 buffer;
it is the differential-test oracle (and the "flat decode" baseline the
benchmark times) — with ``identity_scales=True`` rows are snapped to the
int8 grid at write time, so tiered reads are bit-identical to flat reads
through any promote/demote history.

The flat mirror is kept unconditionally: at repro scale it costs one extra
scatter per write and an (n_pages, D) f32 buffer, and in exchange every
store — not just verify-mode engines — can be differentially probed
(``lookup_flat`` / ``max_abs_error``) by tests and the benchmark's
baseline. A memory-constrained deployment would gate it behind a flag.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

import functools

import jax

from repro.kernels.tiered_gather.ops import (
    gather_rows,
    tiered_lookup_counted,
    tiered_lookup_segments,
)

NEAR, FAR = 0, 1
_QMAX = 127.0

# segment roles for mixed prefill/decode dispatches (continuous batching):
# the engine tags every segment with the phase of work it carries, and the
# counter plane keeps a (role, tier) accumulator next to slot/tenant ones
ROLE_DECODE, ROLE_PREFILL = 0, 1
N_ROLES = 2


@functools.partial(jax.jit, static_argnames=())
def _plane_add(ctr_slot, ctr_tenant, ctr_role, ctr_total, hits, slot_vec,
               tenant_vec, role_vec):
    """Fold one dispatch's per-segment hit pairs into the counter plane —
    pure device arithmetic, no host sync. Padded segments carry zero hits,
    so scatter-adding them anywhere is a no-op."""
    return (
        ctr_slot.at[slot_vec].add(hits),
        ctr_tenant.at[tenant_vec].add(hits),
        ctr_role.at[role_vec].add(hits),
        ctr_total + hits.sum(axis=0),
    )


def _bucket(n: int, floor: int = 32) -> int:
    """Next power-of-two padding bucket: keeps the ragged concat's jitted
    shapes to O(log N) variants instead of one per distinct step size."""
    return max(floor, 1 << (int(n) - 1).bit_length())


def sanitize_near_ids(near_ids, n_pages: int, capacity: int) -> np.ndarray:
    """Canonical near-set sanitizer shared by the engine's apply_placement
    and TieredKVCache.migrate — the two views MUST apply the same rule or
    placement.tier and the device tier map silently diverge: drop
    out-of-range ids, dedup keeping first-seen order, then cut to capacity."""
    ids = np.asarray(near_ids, np.int64).reshape(-1)
    ids = ids[(ids >= 0) & (ids < n_pages)]
    ids = ids[np.sort(np.unique(ids, return_index=True)[1])]
    return ids[:capacity]


class TieredKVCache:
    def __init__(
        self,
        n_pages: int,
        row_dim: int,
        near_capacity: int,
        *,
        near_dtype=jnp.float32,
        identity_scales: bool = False,
        interpret: Optional[bool] = None,
        counter_slots: int = 0,
        device=None,
    ):
        assert 0 < near_capacity <= n_pages
        self.n_pages = n_pages
        self.row_dim = row_dim
        self.near_capacity = near_capacity
        self.identity_scales = identity_scales
        self.interpret = interpret
        # the device that holds this store (None: JAX's default device).
        # Committing the stores there makes every later update and lookup
        # run there too; only incoming payload rows need moving (write).
        self.device = device
        # device stores
        self.near = self._put(jnp.zeros((near_capacity, row_dim), near_dtype))
        self.far_q = self._put(jnp.zeros((n_pages, row_dim), jnp.int8))
        self.far_scale = self._put(jnp.ones((n_pages,), jnp.float32))
        self.flat = self._put(jnp.zeros((n_pages, row_dim), jnp.float32))
        # host mirrors of the device maps (slot allocation is host-side
        # bookkeeping, exactly like the page table itself)
        self.tier_host = np.full(n_pages, FAR, np.int32)
        self.slot_host = np.arange(n_pages, dtype=np.int32)  # far slot == pid
        self._free_near = list(range(near_capacity - 1, -1, -1))
        self._maps_dirty = True
        self._tier_dev = None
        self._slot_dev = None
        # counters (host books: drained totals plus legacy per-call sums)
        self.near_hits = 0
        self.far_hits = 0
        self.lookups = 0
        self.moved_rows = 0
        self.moved_bytes = 0
        self.writes = 0
        # dispatch/sync budget: kernel launches issued and host round-trips
        # paid — the two quantities the single-dispatch decode step minimizes
        self.dispatches = 0
        self.host_syncs = 0
        self.drains = 0
        # device-resident counter plane: (k, 2) int32 accumulators of
        # (near, far) hit pairs. The slot plane is indexed by engine decode
        # slot, the tenant plane by a caller-assigned tenant index; both
        # grow on demand and are only read by drain_counters().
        self.ctr_slot = self._put(jnp.zeros((int(counter_slots), 2), jnp.int32))
        self.ctr_tenant = self._put(jnp.zeros((0, 2), jnp.int32))
        # per-ROLE accumulator: row 0 = decode segments, row 1 = prefill
        # chunks — the continuous-batching step carries a role alongside
        # each segment index so mixed prefill/decode dispatches stay
        # attributable without a second kernel pass
        self.ctr_role = self._put(jnp.zeros((N_ROLES, 2), jnp.int32))
        self.ctr_total = self._put(jnp.zeros((2,), jnp.int32))
        self._plane_dirty = False
        # degraded far-tier-only mode: the near tier is capacity-zeroed at
        # runtime (host poisoned / HBM partition lost). While set, every
        # migrate resolves to the EMPTY near set — demote-only — so no
        # placement push can land rows in a tier the failover declared dead.
        self.degraded = False

    # ------------------------------------------------------------------
    def _put(self, x):
        return x if self.device is None else jax.device_put(x, self.device)

    @property
    def near_row_bytes(self) -> int:
        """Bytes a promotion writes into the near tier (f32/bf16 row)."""
        return self.row_dim * self.near.dtype.itemsize

    @property
    def far_row_bytes(self) -> int:
        """Bytes a demotion writes into the far tier (int8 row + scale)."""
        return self.row_dim + 4

    @property
    def near_count(self) -> int:
        return int((self.tier_host == NEAR).sum())

    def _device_maps(self):
        if self._maps_dirty:
            self._tier_dev = jnp.asarray(self.tier_host)
            self._slot_dev = jnp.asarray(self.slot_host)
            self._maps_dirty = False
        return self._tier_dev, self._slot_dev

    def _quantize(self, rows: jnp.ndarray):
        """Per-row symmetric int8 quantization (identity scales: scale=1)."""
        rows = rows.astype(jnp.float32)
        if self.identity_scales:
            scale = jnp.ones((rows.shape[0],), jnp.float32)
        else:
            absmax = jnp.max(jnp.abs(rows), axis=1)
            scale = jnp.maximum(absmax, 1e-30) / _QMAX
        q = jnp.clip(jnp.round(rows / scale[:, None]), -_QMAX, _QMAX).astype(jnp.int8)
        return q, scale

    def snap(self, rows: jnp.ndarray) -> jnp.ndarray:
        """Snap payload rows onto the representable grid.

        Under identity scales that is the int8 integer grid — the
        "quantization error zeroed" mode the equivalence oracle runs in;
        otherwise rows pass through unchanged (far-tier storage is lossy
        and the round-trip error is bounded by scale/2 per element).
        """
        rows = rows.astype(jnp.float32)
        if self.identity_scales:
            rows = jnp.clip(jnp.round(rows), -_QMAX, _QMAX)
        return rows

    # ------------------------------------------------------------------
    def write(self, page_ids, rows):
        """Write payload rows for ``page_ids`` into their CURRENT tier.

        Near pages land in their near slot at full precision; far pages are
        quantized into their reserved far slot. ``flat`` (the legacy flat
        buffer / differential oracle) always receives the full-precision row.
        Duplicate ids keep the last row (page-table writes are ordered).
        """
        pids = np.asarray(page_ids, np.int64).reshape(-1)
        rows = self.snap(self._put(jnp.asarray(rows)).reshape(pids.size, self.row_dim))
        if pids.size == 0:
            return
        # keep the LAST write per page id
        _, last = np.unique(pids[::-1], return_index=True)
        keep = (pids.size - 1) - last
        pids, rows = pids[keep], rows[jnp.asarray(keep)]
        self.flat = self.flat.at[pids].set(rows)
        near_mask = self.tier_host[pids] == NEAR
        if near_mask.any():
            np_ids = pids[near_mask]
            nrows = rows[jnp.asarray(np.flatnonzero(near_mask))]
            self.near = self.near.at[self.slot_host[np_ids]].set(
                nrows.astype(self.near.dtype)
            )
        if (~near_mask).any():
            fp_ids = pids[~near_mask]
            frows = rows[jnp.asarray(np.flatnonzero(~near_mask))]
            q, scale = self._quantize(frows)
            self.far_q = self.far_q.at[fp_ids].set(q)
            self.far_scale = self.far_scale.at[fp_ids].set(scale)
        self.writes += int(pids.size)

    # ------------------------------------------------------------------
    def lookup(self, page_ids):
        """Gather payload rows for ``page_ids`` through the fused tiered
        kernel. Returns (rows (N, D) f32, near_hits int, far_hits int) —
        the hit split counted on device, at the access point.

        The counters are synced to host ints per call because the engine
        charges them to per-slot tenant books immediately; a
        latency-critical deployment would keep them on device and drain
        once per step."""
        ids = jnp.asarray(np.asarray(page_ids, np.int64).reshape(-1), jnp.int32)
        tier, slot = self._device_maps()
        rows, near, far = tiered_lookup_counted(
            self.near, self.far_q, self.far_scale, tier, slot, ids,
            interpret=self.interpret,
        )
        n, f = int(near), int(far)
        self.near_hits += n
        self.far_hits += f
        self.lookups += 1
        self.dispatches += 1
        self.host_syncs += 1
        return rows, n, f

    # ------------------------------------------------------------------
    def ensure_counter_plane(self, n_slots: int, n_tenants: int):
        """Grow the counter plane to at least (n_slots, n_tenants) rows,
        preserving any undrained counts."""

        def grow(buf, k):
            if buf.shape[0] >= k:
                return buf
            return jnp.concatenate(
                [buf, jnp.zeros((k - buf.shape[0], 2), jnp.int32)]
            )

        self.ctr_slot = grow(self.ctr_slot, int(n_slots))
        self.ctr_tenant = grow(self.ctr_tenant, int(n_tenants))

    def lookup_segments(self, page_ids, seg_of, n_segments: int,
                        slot_idx=None, tenant_idx=None, role_idx=None):
        """Step-wide ragged gather: ONE kernel dispatch, ZERO host syncs.

        ``page_ids`` concatenates every segment's pages; ``seg_of`` assigns
        each gather to a segment in [0, n_segments - 1) — the last segment
        index is reserved for shape-bucketing padding and its counts are
        discarded. ``slot_idx``/``tenant_idx``/``role_idx`` (one index per
        real segment) route the per-segment (near, far) hit pairs into the
        device counter plane, where they accumulate until
        :meth:`drain_counters`. ``role_idx`` carries the segment's phase
        (ROLE_DECODE / ROLE_PREFILL) so a continuous-batching step that
        mixes decode walks with prefill-chunk reads in the SAME dispatch
        stays attributable per phase; omitted, every segment charges the
        decode row.

        Returns the gathered rows (N, D) f32 — a device array; the hit
        counters never touch the host here.
        """
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        seg = np.asarray(seg_of, np.int32).reshape(-1)
        assert seg.size == ids.size
        n_segments = int(n_segments)
        # the last segment is the padding sink: real gathers assigned there
        # would be silently dropped from the books, so fail loudly instead
        assert int(seg.max(initial=-1)) < n_segments - 1, (
            f"seg_of uses segment {int(seg.max(initial=-1))} but n_segments="
            f"{n_segments} reserves the last index for padding"
        )
        if ids.size == 0:
            return jnp.zeros((0, self.row_dim), jnp.float32)
        # pad the ragged concat to a power-of-two bucket; padding gathers
        # page 0 into the sacrificial last segment, whose counts are dropped
        pad = _bucket(ids.size) - ids.size
        if pad:
            ids = np.concatenate([ids, np.zeros(pad, np.int64)])
            seg = np.concatenate([seg, np.full(pad, n_segments - 1, np.int32)])
        tier, slot = self._device_maps()
        rows, seg_hits = tiered_lookup_segments(
            self.near, self.far_q, self.far_scale, tier, slot,
            jnp.asarray(ids, jnp.int32), jnp.asarray(seg), n_segments,
            interpret=self.interpret,
        )
        live = seg_hits[: n_segments - 1]
        k = live.shape[0]
        slot_vec = np.zeros(k, np.int32)
        tenant_vec = np.zeros(k, np.int32)
        role_vec = np.zeros(k, np.int32)  # default: everything is decode
        if slot_idx is not None:
            slot_vec[: len(slot_idx)] = np.asarray(slot_idx, np.int32)
        if tenant_idx is not None:
            tenant_vec[: len(tenant_idx)] = np.asarray(tenant_idx, np.int32)
        if role_idx is not None:
            role_vec[: len(role_idx)] = np.asarray(role_idx, np.int32)
            assert role_vec.min() >= 0 and role_vec.max() < N_ROLES, role_vec
        self.ensure_counter_plane(int(slot_vec.max(initial=-1)) + 1,
                                  int(tenant_vec.max(initial=-1)) + 1)
        self.ctr_slot, self.ctr_tenant, self.ctr_role, self.ctr_total = _plane_add(
            self.ctr_slot, self.ctr_tenant, self.ctr_role, self.ctr_total,
            live, jnp.asarray(slot_vec), jnp.asarray(tenant_vec),
            jnp.asarray(role_vec),
        )
        self._plane_dirty = True
        self.lookups += 1
        self.dispatches += 1
        return rows[: ids.size - pad] if pad else rows

    def drain_counters(self, discard: bool = False) -> dict:
        """The ONE host sync of the counter plane: materialize the per-slot
        / per-tenant / total accumulators, zero them, and fold the totals
        into the host hit books. Draining every step or once per window
        charges identical books — the plane is a pure sum — which is the
        invariant the drain-equivalence test pins.

        Idempotent: a clean (never-accumulated or already-drained) plane
        returns all-zero deltas and charges NOTHING — no host sync, no
        drain tick, no recharge — so crash/teardown paths may drain
        defensively without corrupting the books. Safe on a partially-
        initialized store (constructor interrupted before the plane
        existed): treated as clean.

        ``discard=True`` is the crash path: the deltas are materialized
        and the plane zeroed, but the totals are QUARANTINED — not folded
        into the host hit books and not charged as a host sync — because
        they describe work a dead host never reported. The caller owns
        them as the ``lost_window``; a subsequent normal drain sees a
        clean plane and returns zeros, so the lost counts can never leak
        back into the fleet merge.
        """
        if not getattr(self, "_plane_dirty", False):
            n_slots = self.ctr_slot.shape[0] if hasattr(self, "ctr_slot") else 0
            n_tenants = self.ctr_tenant.shape[0] if hasattr(self, "ctr_tenant") else 0
            return {
                "near": 0,
                "far": 0,
                "slot": np.zeros((n_slots, 2), np.int64),
                "tenant": np.zeros((n_tenants, 2), np.int64),
                "role": np.zeros((N_ROLES, 2), np.int64),
            }
        slot_c, tenant_c, role_c, total = (
            np.asarray(x, np.int64)
            for x in jax.device_get(
                (self.ctr_slot, self.ctr_tenant, self.ctr_role, self.ctr_total)
            )
        )
        self.ctr_slot = self._put(jnp.zeros_like(self.ctr_slot))
        self.ctr_tenant = self._put(jnp.zeros_like(self.ctr_tenant))
        self.ctr_role = self._put(jnp.zeros_like(self.ctr_role))
        self.ctr_total = self._put(jnp.zeros_like(self.ctr_total))
        self._plane_dirty = False
        n, f = int(total[0]), int(total[1])
        if not discard:
            self.near_hits += n
            self.far_hits += f
            self.host_syncs += 1
            self.drains += 1
        return {"near": n, "far": f, "slot": slot_c, "tenant": tenant_c,
                "role": role_c}

    def lookup_flat(self, page_ids):
        """The legacy flat-buffer gather (baseline + differential oracle)."""
        ids = jnp.asarray(np.asarray(page_ids, np.int64).reshape(-1), jnp.int32)
        return gather_rows(self.flat, ids, interpret=self.interpret)

    def max_abs_error(self, page_ids) -> float:
        """Tiered-vs-flat read divergence for ``page_ids`` (0.0 under
        identity scales). Diagnostic only: bypasses the hit counters so a
        probe never perturbs the ground-truth accounting."""
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        if ids.size == 0:
            return 0.0
        tier, slot = self._device_maps()
        rows, _, _ = tiered_lookup_counted(
            self.near, self.far_q, self.far_scale, tier, slot,
            jnp.asarray(ids, jnp.int32), interpret=self.interpret,
        )
        return float(jnp.max(jnp.abs(rows - self.lookup_flat(ids))))

    # ------------------------------------------------------------------
    def set_degraded(self, flag: bool):
        """Flip far-tier-only mode. Entering does not move data by itself —
        callers follow with ``migrate(())`` to demote the resident near rows
        (ServingEngine.enter_degraded does both under one accounting
        boundary)."""
        self.degraded = bool(flag)

    # ------------------------------------------------------------------
    def migrate(self, near_ids, account: bool = True) -> dict:
        """Reconcile the device tiers with a planned near set — REAL moves.

        Demotions run first (quantize near row -> its reserved far slot,
        freeing the near slot), then promotions (dequantize far row -> a
        free near slot). Total pages are conserved by construction (tier is
        a total map) and the near tier never exceeds ``near_capacity``.
        Returns {"promoted", "demoted", "moved_rows", "moved_bytes"}.

        ``account=False`` skips the moved_rows/moved_bytes accumulators:
        the constructor-time initial fill loads empty rows into position,
        it is not migration traffic.

        While ``degraded`` the planned near set is forced EMPTY: resident
        near rows demote (data preserved through the quantize path — the
        capacity is what died, not the bits already read out) and no
        promotion can land, whatever the caller planned.
        """
        want = np.zeros(self.n_pages, bool)
        if not self.degraded:
            want[sanitize_near_ids(near_ids, self.n_pages, self.near_capacity)] = True
        cur = self.tier_host == NEAR
        demote = np.flatnonzero(cur & ~want)
        promote = np.flatnonzero(~cur & want)
        if demote.size:
            d_slots = self.slot_host[demote].copy()
            rows = self.near[jnp.asarray(d_slots)].astype(jnp.float32)
            q, scale = self._quantize(rows)
            self.far_q = self.far_q.at[demote].set(q)
            self.far_scale = self.far_scale.at[demote].set(scale)
            self.tier_host[demote] = FAR
            self.slot_host[demote] = demote  # far slot == page id
            self._free_near.extend(int(s) for s in d_slots)
        if promote.size:
            assert len(self._free_near) >= promote.size, "near tier overflow"
            slots = np.array([self._free_near.pop() for _ in range(promote.size)], np.int32)
            rows = self.far_q[jnp.asarray(promote)].astype(jnp.float32) * self.far_scale[
                jnp.asarray(promote)
            ][:, None]
            self.near = self.near.at[jnp.asarray(slots)].set(rows.astype(self.near.dtype))
            self.tier_host[promote] = NEAR
            self.slot_host[promote] = slots
        if demote.size or promote.size:
            self._maps_dirty = True
        moved = int(promote.size + demote.size)
        # bytes written into the destination tier: promotions land full-
        # precision rows in near, demotions land int8 rows + a scale in far
        moved_bytes = int(
            promote.size * self.near_row_bytes + demote.size * self.far_row_bytes
        )
        if account:
            self.moved_rows += moved
            self.moved_bytes += moved_bytes
        return {
            "promoted": int(promote.size),
            "demoted": int(demote.size),
            "moved_rows": moved,
            "moved_bytes": moved_bytes,
        }

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Host-book snapshot. ``near_hits``/``far_hits`` report DRAINED
        counts only — callers owning undrained segmented lookups (the
        serving engine) drain before reading."""
        tot = self.near_hits + self.far_hits
        return {
            "near_count": self.near_count,
            "near_capacity": self.near_capacity,
            "near_hits": self.near_hits,
            "far_hits": self.far_hits,
            "near_hit_rate": self.near_hits / max(tot, 1),
            "lookups": self.lookups,
            "writes": self.writes,
            "moved_rows": self.moved_rows,
            "moved_bytes": self.moved_bytes,
            "dispatches": self.dispatches,
            "host_syncs": self.host_syncs,
            "drains": self.drains,
        }

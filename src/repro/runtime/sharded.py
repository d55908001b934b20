"""Sharded serving: ONE logical replica spanning chips.

``ShardedServingEngine`` tensor-shards a replica over the ``model`` axis of
a serving mesh (``launch.mesh.make_serving_mesh``; CPU-testable under
``XLA_FLAGS=--xla_force_host_platform_device_count=N``):

* **parameters** are placed once via ``shard_model_params`` — each leaf's
  last axis partitioned over ``model`` when divisible (NamedSharding),
  replicated otherwise — so every jitted step computes on sharded operands
  with no per-call constraint traffic;
* **KV pages** are partitioned PAGE-INTERLEAVED across per-shard
  ``TieredKVCache`` slices: shard ``s`` owns every page with
  ``pid % n_shards == s`` (local id ``pid // n_shards``). Interleaving —
  not feature-dim splitting — is what makes the counter algebra work: each
  page's near/far hit is counted by EXACTLY ONE shard, so summing the
  shards' drained planes reproduces the unsharded engine's counters
  bit-for-bit (feature-sharding the rows would have every shard count
  every hit N times over).

The step budget is unchanged in shape: ONE segmented tiered-gather
dispatch per shard per step (a shard with no pages in the step's walk pays
zero — ``TieredKVCache.lookup_segments`` never launches on an empty id
set) and ZERO mandatory host syncs — each shard keeps its own device
counter plane and drains it independently once per profiler window; a
clean plane's drain early-returns without a sync, so idle shards do not
even pay the window sync.

Drain/merge contract (the PR-5 invariant, per shard): every shard's plane
is a pure sum, so the facade's ``drain_counters`` merges the per-shard
drains by summation into ONE dict with the unsharded shape — placement
stats, tenant books, role accumulators and the MemProf export all see a
single logical store, and the books are bit-identical at any drain
cadence. Per-shard (near, far) deltas are additionally accumulated for the
flight recorder: the engine charges them to ``shard_near_hits{shard=s}`` /
``shard_far_hits{shard=s}`` registry counters, which merge bit-exactly
across replicas like every other counter (sums of sums).

Per-shard near capacity is ``min(pages_owned, global_near_capacity)``:
the planner's global near set restricted to shard ``s`` can never exceed
either bound, so ``sanitize_near_ids``'s silent capacity cut can never
fire on a shard and the per-shard tier maps stay exact restrictions of
``placement.tier``.

Equivalence anchors (tests/test_sharded.py): a 1-shard mesh is bit-exact
with ``ServingEngine`` — same tokens, same drained counters, same tenant
books — and N-shard merged counters equal the 1-shard totals on the same
seeded request stream (the counter path depends on page walks, never on
generated token VALUES, so the equality survives cross-shard float
reassociation in the model math).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import (
    activate,
    make_serving_mesh,
    model_shardings,
    shard_model_params,
)
from repro.runtime.serving import EngineConfig, ServingEngine
from repro.runtime.tiered_kv import (
    N_ROLES,
    TieredKVCache,
    sanitize_near_ids,
)


def _padded_sum(arrays: List[np.ndarray]) -> np.ndarray:
    """Sum (k_i, 2) int64 arrays of unequal first dims (planes grow on
    demand per shard) into one (max k_i, 2) array."""
    k = max((a.shape[0] for a in arrays), default=0)
    out = np.zeros((k, 2), np.int64)
    for a in arrays:
        out[: a.shape[0]] += a
    return out


class ShardedTieredKV:
    """Per-shard ``TieredKVCache`` slices behind the unsharded interface.

    The serving engine talks to this exactly as it talks to one
    ``TieredKVCache``: global page ids in, merged counters out. Every
    method splits ids by ``pid % n_shards``, forwards local ids
    (``pid // n_shards``) to the owning shard, and merges results by pure
    summation — the decomposition the PR-5 counter-plane invariant makes
    exact at any drain cadence.
    """

    def __init__(
        self,
        n_pages: int,
        row_dim: int,
        near_capacity: int,
        n_shards: int,
        *,
        near_dtype=jnp.float32,
        identity_scales: bool = False,
        interpret: Optional[bool] = None,
        counter_slots: int = 0,
        devices=None,
    ):
        if n_shards < 1 or n_pages % n_shards != 0:
            raise ValueError(
                f"n_shards={n_shards} must divide n_pages={n_pages}: the "
                "page-interleaved partition owns pages by pid % n_shards"
            )
        self.n_pages = n_pages
        self.row_dim = row_dim
        self.near_capacity = near_capacity  # the GLOBAL planner capacity
        self.n_shards = n_shards
        self.identity_scales = identity_scales
        self.interpret = interpret
        n_local = n_pages // n_shards
        # shard s lives on devices[s] (None: every shard on the default
        # device); merged rows are gathered onto the first shard's device
        devices = list(devices) if devices is not None else [None] * n_shards
        if len(devices) != n_shards:
            raise ValueError(f"{len(devices)} devices for {n_shards} shards")
        self.home = devices[0]
        self.shards = [
            TieredKVCache(
                n_local,
                row_dim,
                min(n_local, near_capacity),
                near_dtype=near_dtype,
                identity_scales=identity_scales,
                interpret=interpret,
                counter_slots=counter_slots,
                device=dev,
            )
            for dev in devices
        ]
        # per-shard drained (near, far) deltas pending consumption by the
        # engine's shard-labeled metric rows (take_shard_drains)
        self._shard_drained = [{"near": 0, "far": 0} for _ in range(n_shards)]

    # ------------------------------------------------------------------
    # summed host books (the unsharded attribute surface)

    def _sum(self, attr: str) -> int:
        return sum(getattr(sh, attr) for sh in self.shards)

    @property
    def near_hits(self) -> int:
        return self._sum("near_hits")

    @property
    def far_hits(self) -> int:
        return self._sum("far_hits")

    @property
    def lookups(self) -> int:
        return self._sum("lookups")

    @property
    def writes(self) -> int:
        return self._sum("writes")

    @property
    def moved_rows(self) -> int:
        return self._sum("moved_rows")

    @property
    def moved_bytes(self) -> int:
        return self._sum("moved_bytes")

    @property
    def dispatches(self) -> int:
        return self._sum("dispatches")

    @property
    def host_syncs(self) -> int:
        return self._sum("host_syncs")

    @property
    def drains(self) -> int:
        return self._sum("drains")

    @property
    def near_count(self) -> int:
        return self._sum("near_count")

    # ------------------------------------------------------------------
    def _owner(self, ids: np.ndarray) -> np.ndarray:
        return ids % self.n_shards

    def _merge_rows(self, n: int, parts) -> jnp.ndarray:
        """Scatter per-shard ``(positions, rows)`` back into global order,
        on the home device."""
        out = jnp.zeros((n, self.row_dim), jnp.float32)
        for idx, rows in parts:
            if self.home is not None:
                rows = jax.device_put(rows, self.home)
            out = out.at[jnp.asarray(idx)].set(rows)
        return out

    def snap(self, rows):
        return self.shards[0].snap(rows)

    def write(self, page_ids, rows):
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        if ids.size == 0:
            return
        rows = jnp.asarray(rows).reshape(ids.size, self.row_dim)
        owner = self._owner(ids)
        for s, sh in enumerate(self.shards):
            idx = np.flatnonzero(owner == s)
            if idx.size:
                sh.write(ids[idx] // self.n_shards, rows[jnp.asarray(idx)])

    def ensure_counter_plane(self, n_slots: int, n_tenants: int):
        for sh in self.shards:
            sh.ensure_counter_plane(n_slots, n_tenants)

    def lookup_segments(self, page_ids, seg_of, n_segments: int,
                        slot_idx=None, tenant_idx=None, role_idx=None):
        """Step-wide ragged gather, ONE dispatch per NON-EMPTY shard.

        Each shard receives its own pages with the ORIGINAL segment
        indices and the same slot/tenant/role routing vectors, pads its
        own ragged concat, and accumulates its own device counter plane —
        no cross-shard sync anywhere. Because every page id lands in
        exactly one shard, the per-segment hit pairs across shards are a
        disjoint partition of the unsharded pairs: their drained sum is
        bit-identical to one store's counts.
        """
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        seg = np.asarray(seg_of, np.int32).reshape(-1)
        if ids.size == 0:
            return jnp.zeros((0, self.row_dim), jnp.float32)
        parts = []
        owner = self._owner(ids)
        for s, sh in enumerate(self.shards):
            idx = np.flatnonzero(owner == s)
            if idx.size == 0:
                continue  # idle shard: zero dispatches this step
            rows = sh.lookup_segments(
                ids[idx] // self.n_shards, seg[idx], n_segments,
                slot_idx=slot_idx, tenant_idx=tenant_idx, role_idx=role_idx,
            )
            parts.append((idx, rows))
        return self._merge_rows(ids.size, parts)

    def lookup(self, page_ids):
        """Per-call (baseline) path: fan out, merge rows + host-int hits."""
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        parts = []
        near = far = 0
        owner = self._owner(ids)
        for s, sh in enumerate(self.shards):
            idx = np.flatnonzero(owner == s)
            if idx.size == 0:
                continue
            r, n, f = sh.lookup(ids[idx] // self.n_shards)
            parts.append((idx, r))
            near += n
            far += f
        return self._merge_rows(ids.size, parts), near, far

    def lookup_flat(self, page_ids):
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        owner = self._owner(ids)
        parts = []
        for s, sh in enumerate(self.shards):
            idx = np.flatnonzero(owner == s)
            if idx.size:
                parts.append((idx, sh.lookup_flat(ids[idx] // self.n_shards)))
        return self._merge_rows(ids.size, parts)

    def max_abs_error(self, page_ids) -> float:
        ids = np.asarray(page_ids, np.int64).reshape(-1)
        owner = self._owner(ids)
        err = 0.0
        for s, sh in enumerate(self.shards):
            idx = np.flatnonzero(owner == s)
            if idx.size:
                err = max(err, sh.max_abs_error(ids[idx] // self.n_shards))
        return err

    # ------------------------------------------------------------------
    def drain_counters(self, discard: bool = False) -> dict:
        """Drain every shard's plane independently and merge by summation.

        One host sync per DIRTY shard (a clean shard's drain early-returns
        sync-free), once per profiler window — never per step. The merged
        dict has the unsharded shape, so placement stats, tenant books and
        the role accumulator charge exactly as before; per-shard (near,
        far) deltas accumulate for ``take_shard_drains``.

        ``discard=True`` quarantines every shard's deltas (the crash-path
        ``lost_window`` semantics of TieredKVCache.drain_counters): no
        shard books or shard-drain feed are charged.
        """
        drains = [sh.drain_counters(discard=discard) for sh in self.shards]
        if discard:
            role = np.zeros((N_ROLES, 2), np.int64)
            for d in drains:
                role += np.asarray(d["role"], np.int64)
            return {
                "near": sum(d["near"] for d in drains),
                "far": sum(d["far"] for d in drains),
                "slot": _padded_sum([np.asarray(d["slot"], np.int64) for d in drains]),
                "tenant": _padded_sum([np.asarray(d["tenant"], np.int64) for d in drains]),
                "role": role,
            }
        for s, d in enumerate(drains):
            self._shard_drained[s]["near"] += d["near"]
            self._shard_drained[s]["far"] += d["far"]
        role = np.zeros((N_ROLES, 2), np.int64)
        for d in drains:
            role += np.asarray(d["role"], np.int64)
        return {
            "near": sum(d["near"] for d in drains),
            "far": sum(d["far"] for d in drains),
            "slot": _padded_sum([np.asarray(d["slot"], np.int64) for d in drains]),
            "tenant": _padded_sum([np.asarray(d["tenant"], np.int64) for d in drains]),
            "role": role,
        }

    def take_shard_drains(self) -> List[dict]:
        """Per-shard drained (near, far) deltas since the last take — the
        feed for shard-labeled flight-recorder counters (pure sums, so the
        labeled rows merge bit-exactly at any cadence)."""
        out = self._shard_drained
        self._shard_drained = [{"near": 0, "far": 0} for _ in self.shards]
        return out

    # ------------------------------------------------------------------
    @property
    def degraded(self) -> bool:
        return all(sh.degraded for sh in self.shards)

    def set_degraded(self, flag: bool):
        """Fan far-tier-only mode out to every shard: one logical replica
        degrades as a unit (the mesh that lost its near capacity is shared
        by all shards of the replica)."""
        for sh in self.shards:
            sh.set_degraded(flag)

    # ------------------------------------------------------------------
    def migrate(self, near_ids, account: bool = True) -> dict:
        """Reconcile every shard with the GLOBAL planned near set: shard
        ``s`` receives the set restricted to its own pages (guaranteed to
        fit its capacity — see the module header). Results sum."""
        ids = sanitize_near_ids(near_ids, self.n_pages, self.near_capacity)
        owner = self._owner(ids)
        out = {"promoted": 0, "demoted": 0, "moved_rows": 0, "moved_bytes": 0}
        for s, sh in enumerate(self.shards):
            res = sh.migrate(ids[owner == s] // self.n_shards, account=account)
            for k in out:
                out[k] += res[k]
        return out

    # ------------------------------------------------------------------
    def stats(self) -> dict:
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
            # sharding surface: per-shard near ceilings feed the
            # AutoTierer's TierEpoch.shard_near_capacity
            "shards": self.n_shards,
            "shard_near_capacity": [sh.near_capacity for sh in self.shards],
            "shard_dispatches": [sh.dispatches for sh in self.shards],
            "shard_near_hits": [sh.near_hits for sh in self.shards],
            "shard_far_hits": [sh.far_hits for sh in self.shards],
        }


class ShardedServingEngine(ServingEngine):
    """A ``ServingEngine`` whose params and KV pages span a device mesh.

    One logical replica, one routing target: the fleet wraps it in a
    ``Replica`` like any other engine — its profile export, tenant books
    and metrics are the merged (summed) view of its shards. Construction
    places the parameters on the mesh (``shard_model_params``); the tiered
    store comes from the ``_make_tiered_store`` seam as a
    ``ShardedTieredKV``; every step runs under the activated mesh so model
    code's ``shard()`` constraints bind.
    """

    def __init__(
        self,
        api,
        params,
        ecfg: EngineConfig,
        seed: int = 0,
        recorder=None,
        mesh=None,
    ):
        n = max(1, int(ecfg.model_shards))
        if ecfg.n_pages % n != 0:
            raise ValueError(
                f"model_shards={n} must divide n_pages={ecfg.n_pages}"
            )
        self.mesh = mesh if mesh is not None else make_serving_mesh(n)
        if int(self.mesh.shape["model"]) != n:
            raise ValueError(
                f"mesh model axis {self.mesh.shape['model']} != "
                f"model_shards={n}"
            )
        with activate(self.mesh):
            params = shard_model_params(params, self.mesh)
            super().__init__(api, params, ecfg, seed=seed, recorder=recorder)

    def _make_tiered_store(self):
        e = self.ecfg
        return ShardedTieredKV(
            e.n_pages,
            self._payload_dim(),
            self.placement.near_capacity,
            max(1, int(e.model_shards)),
            identity_scales=e.tiered_identity_scales,
            counter_slots=e.max_batch,
            devices=list(self.mesh.devices.flat),
        )

    def _make_cache(self):
        # the slot cache takes the parameters' layout on the mesh, built
        # in place rather than on the default device and then moved
        e = self.ecfg
        shardings = model_shardings(
            self.api.abstract_cache(e.max_batch, e.max_len), self.mesh
        )
        return jax.jit(
            lambda: self.api.init_cache(e.max_batch, e.max_len),
            out_shardings=shardings,
        )()

    def step(self) -> int:
        # the whole step — admit, chunk/decode dispatch, segmented gather,
        # boundary drain — runs under the mesh so sharding constraints in
        # model code resolve against it; nothing else changes
        with activate(self.mesh):
            return super().step()

    def drain_tier_counters(self):
        d = super().drain_tier_counters()
        if isinstance(self.tiered, ShardedTieredKV):
            # shard-labeled metric rows: drained deltas are pure sums, so
            # these counters merge bit-exactly across cadences and replicas
            for s, delta in enumerate(self.tiered.take_shard_drains()):
                if delta["near"]:
                    self.metrics.counter("shard_near_hits", shard=str(s)).inc(
                        delta["near"]
                    )
                if delta["far"]:
                    self.metrics.counter("shard_far_hits", shard=str(s)).inc(
                        delta["far"]
                    )
        return d

"""Serving engine: continuous batching over paged, tiered, prefix-shared KV.

This is where the paper's three findings operate together at runtime:

  * shared KV page table (core/pagetable): requests with common prompt
    prefixes map the same physical pages (multi-ASID I-TLB analogue) —
    dedups HBM capacity and prefill traffic;
  * tiered placement (core/placement): hot pages stay in the HBM near tier,
    cold pages demote to the host far tier, driven by windowed access counts
    from the profiler (MemProf.MemBW in the loop);
  * software prefetch (core/prefetch): the decode step's sequential page walk
    is predicted and far pages are fetched ahead, overlapping transfer with
    compute; accuracy/coverage accounted with the paper's formulas.

Model math runs through the model's own decode_step (exact for every
family); the page table is the management/accounting plane, as in any
engine where the block manager is host-side (vLLM-style). The Pallas
paged_attention kernel is the device-side fast path for dense archs
(examples/serve_tiered.py wires it directly).

Device-executed tiering (``EngineConfig.device_tiering``, env
``REPRO_DEVICE_TIERING=1``): the decode step's KV page stream is EXECUTED
against a device-resident tiered store (runtime/tiered_kv.TieredKVCache) —
near rows in an f32 "HBM" buffer, far rows int8-quantized with per-row
scales — via the fused kernels/tiered_gather pass. The model's own decode
math stays exact and untouched (it reads its per-family cache as always);
what moves on device is the tier plane: the page gathers, the int8
promote/demote data movement driven by placement pushes (local TPP epochs
and fleet AutoTierer apply_placement), and the near/far hit counters,
which are produced in-kernel at the access point and REPLACE the
host-side tier accounting. With identity scales the device-tiered engine
is bit-identical to the host-accounted one (same tokens, same counters)
and tiered reads never diverge from the flat mirror;
tests/test_tiered_decode.py enforces that equivalence.

Dispatch/sync budget: one engine step costs ONE tiered-gather dispatch and
ZERO mandatory host syncs. All active slots' page ids are concatenated
into a single ragged (segmented) kernel pass whose per-segment near/far
hit counts accumulate into the store's device counter plane; the engine
drains the plane once per profiler window (``drain_tier_counters``) and
charges placement stats and per-tenant books from the drained deltas —
bit-identically to the retired per-slot path, which is kept as
``EngineConfig.segmented_lookup=False`` for the dispatch-budget
benchmark's baseline. The next-token argmax is fused into the jitted
decode (the step's cache buffers are donated), so the decode feedback loop
stays on device too.

Continuous batching + chunked prefill (``EngineConfig.prefill_chunk``):
with a positive chunk budget, ``step`` is a vLLM-style continuous-batching
step — new requests are admitted into freed slots every step, and their
prompts are fed in fixed-token-budget chunks INTERLEAVED with the decode
tokens of co-resident slots inside the SAME single jitted dispatch (one
(B, C) block pass where the model has one, else a masked column scan over
the family decode step — ``make_chunk_step``; every engine step runs
exactly one model executable and one tiered-gather dispatch regardless of
the prefill/decode mix). Prefill-chunk KV page reads ride the segmented
gather as ROLE_PREFILL segments next to the decode walks, prefill chunks
write KV pages through the tiered write path as they complete, and slot
cache buffers are donated/reused across join/leave churn (a jitted
zero-reset at admit; no per-admit batch-1 cache allocation and no
per-prompt-length XLA compiles — the chunked engine only ever runs two
decode shapes, (B, 1) and (B, C)). ``prefill_chunk = 0`` (the default)
means an infinite budget: prompts prefill whole at admit through
``api.prefill``, the legacy whole-slot path — and the chunk-budget=∞
equivalence baseline.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.workloads import WorkloadProfile
from repro.core.memtrace import MemTracer
from repro.core.pagetable import FAR, NEAR, SharedKVPageTable
from repro.core.placement import TieredPlacement
from repro.core.prefetch import PrefetchEngine, train_tenant_successors
from repro.core.profiler import AccessProfiler
from repro.data.requests import ChunkState, Request, RequestGenerator
from repro.env import env_flag
from repro.obs import PHASES_OFF, Counter, MetricsRegistry, default_recorder
from repro.models.api import ModelAPI, make_serve_step
from repro.runtime.tiered_kv import (
    N_ROLES,
    ROLE_DECODE,
    ROLE_PREFILL,
    TieredKVCache,
    sanitize_near_ids,
)

# families whose decode_step can consume prompt tokens incrementally (the
# chunked-prefill substrate). Excluded: "audio" (whisper's cross-attention
# caches exist only after an encode+prefill pass) and "vlm" (prompt embeds
# carry M-RoPE positions the decode path does not reconstruct) — both fall
# back to monolithic prefill at admit regardless of the chunk budget.
CHUNKABLE_FAMILIES = ("dense", "moe", "ssm", "hybrid")


def _env_device_tiering() -> bool:
    return env_flag("REPRO_DEVICE_TIERING", default=False)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer — the counter-based hash behind the synthetic
    payload rows (vectorized; uint64 wraparound is the intended ring)."""
    x = x.copy()
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def counter_rows(seed: int, page_ids, versions, dim: int) -> np.ndarray:
    """Deterministic standard-normal payload rows keyed on (seed, page,
    write-version), generated by ONE vectorized counter-based draw.

    Replaces a per-page ``np.random.default_rng`` construction loop that
    dominated recurrent-family writes: every output element's uniform bits
    come from splitmix64 over (key, counter), then Box-Muller maps uniform
    pairs to normals — no sequential generator state anywhere.
    """
    pids = np.asarray(page_ids, np.uint64).reshape(-1)
    vers = np.asarray(versions, np.uint64).reshape(-1)
    key = _mix64(
        (np.uint64(seed) << np.uint64(40)) ^ (pids << np.uint64(20)) ^ vers
    )
    ctr = np.arange(2 * dim, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    h = _mix64(key[:, None] ^ ctr[None, :])
    u = (h >> np.uint64(11)).astype(np.float64) * (2.0 ** -53)
    u1 = np.maximum(u[:, :dim], 2.0 ** -53)  # log(0) guard
    u2 = u[:, dim:]
    rows = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    return rows.astype(np.float32)


def _slot_put(dst, src, slot_idx):
    """Write a batch-1 cache leaf into slot ``slot_idx`` of a batched leaf.
    Batch axis differs per leaf family: 1-D leaves (lengths) carry batch on
    axis 0, everything else on axis 1."""
    if dst.ndim == 1:
        return dst.at[slot_idx].set(src[0])
    return dst.at[:, slot_idx].set(src[:, 0])


def _slot_zero(leaf, slot_idx):
    """Zero one slot of a batched cache leaf (same axis rule as _slot_put).
    Chunked admission starts prefill from an empty slot — KV lengths reset
    to 0 and recurrent state cleared — without allocating a fresh cache."""
    if leaf.ndim == 1:
        return leaf.at[slot_idx].set(jnp.zeros((), leaf.dtype))
    return leaf.at[:, slot_idx].set(jnp.zeros((), leaf.dtype))


@jax.jit
def _kv_payload(k, v, slots, positions):
    """Payload rows of n (slot, position) pairs of a (L, B, H, S, D) KV
    cache: each pair's k then v vectors, flattened across layers and heads,
    as (n, 2 * L * H * D) f32.

    One dynamic slice per pair and cache, not one gather: on a TPU an XLA
    gather over the batch and position axes first copies the whole cache
    into another layout, a full copy of k and of v on every call."""
    n_layers, _, n_heads, _, head_dim = k.shape

    def at(c, i):
        start = (0, slots[i], 0, positions[i], 0)
        size = (n_layers, 1, n_heads, 1, head_dim)
        return jax.lax.dynamic_slice(c, start, size).reshape(-1)

    rows = [jnp.concatenate([at(k, i), at(v, i)]) for i in range(slots.shape[0])]
    return jnp.stack(rows).astype(jnp.float32)


def make_chunk_step(api: ModelAPI):
    """The continuous-batching step, ``(params, cache, nxt, tok, use_prompt,
    active, emit) -> (nxt, cache)`` over the (B, C) masks of
    ``ServingEngine._chunk_plan``: prompt rows take their chunk tokens,
    decode rows the fed-back next token in column 0, and a row's active
    columns are a prefix of it. ``emit`` marks the column whose argmax is a
    row's next fed token: column 0 for decode rows, the final-prompt-token
    column for a prompt that completes this step (its first generated
    token); a row without one keeps its ``nxt``. Inactive rows keep their
    cache.

    A family whose model decodes a token block in one pass
    (``api.block_decode``) runs the whole (B, C) block through it. Any other
    family scans the C columns through its single-token decode step,
    gating every cache leaf per column (batch axis 0 for 1-D leaves, else
    axis 1 — the convention ``_write_slot`` relies on).
    """
    vocab = api.cfg.vocab_size
    if api.block_decode:

        def _chunk_step(params, cache, nxt, tok, use_prompt, active, emit):
            n = active.sum(axis=1, dtype=jnp.int32)
            tokens = jnp.where(use_prompt, tok, nxt[:, None])
            out_col = (tok.shape[1] - 1) - jnp.argmax(emit[:, ::-1], axis=1)
            logits, cache = api.decode_block(params, cache, tokens, n, out_col)
            out = jnp.argmax(logits[:, 0, :vocab], axis=-1).astype(jnp.int32)
            return jnp.where(emit.any(axis=1), out, nxt), cache

        return _chunk_step

    serve = make_serve_step(api, vocab=vocab)

    def _chunk_step(params, cache, nxt, tok, use_prompt, active, emit):
        def col(carry, xs):
            cache, nxt = carry
            tok_c, up_c, act_c, em_c = xs
            t = jnp.where(up_c, tok_c, nxt)
            out, new_cache = serve(params, cache, t[:, None])

            def gate(new, old):
                if new.ndim == 1:
                    return jnp.where(act_c, new, old)
                m = act_c.reshape((1, -1) + (1,) * (new.ndim - 2))
                return jnp.where(m, new, old)

            cache = jax.tree.map(gate, new_cache, cache)
            nxt = jnp.where(em_c, out[:, 0], nxt)
            return (cache, nxt), None

        (cache, nxt), _ = jax.lax.scan(
            col, (cache, nxt), (tok.T, use_prompt.T, active.T, emit.T)
        )
        return nxt, cache

    return _chunk_step


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 4
    max_len: int = 256
    page_size: int = 16
    n_pages: int = 1024
    near_frac: float = 0.30
    predictor: str = "nextline"
    prefetch_buffer: int = 64
    placement_window: int = 16  # engine steps per TPP epoch
    trace_window: int = 8
    trace_period: int = 64
    # device-executed tiering: route KV page reads through the fused
    # tiered-gather kernel over a device-resident near/far store
    device_tiering: bool = dataclasses.field(default_factory=_env_device_tiering)
    # one segmented dispatch per step (the default) vs the retired
    # one-dispatch-per-slot path, kept as the dispatch-budget baseline
    segmented_lookup: bool = True
    # snap payload rows to the int8 grid so the far tier is lossless —
    # the "quantization error zeroed" mode of the equivalence oracle
    tiered_identity_scales: bool = False
    # differential probe: compare every tiered read against the flat
    # buffer in-line (tracks the max divergence in stats())
    tiered_verify: bool = False
    # trace-driven far-tier prefetch: at every placement-window boundary,
    # chase each active stream's predictor chain and PROMOTE predicted
    # far pages into the near tier (batched dequant migration through the
    # device store, charged to the migration books) ahead of the decode
    # steps that will read them. Off by default: promotion perturbs the
    # near set, and the lockstep/bit-exact equivalence oracles pin the
    # unperturbed baseline.
    prefetch_promote: bool = False
    # how many predicted transitions ahead of each stream's head to chase
    prefetch_lookahead: int = 4
    # cap on promoted pages per issue window (bounds wasted bandwidth)
    prefetch_max_promote: int = 32
    # tensor-sharding degree of one logical replica: parameters and KV
    # pages partition over the `model` axis of a serving mesh, each shard
    # owning a per-shard TieredKVCache slice (runtime/sharded.py). 1 =
    # today's unsharded engine; ShardedServingEngine consumes this.
    model_shards: int = 1
    # continuous batching: prefill-chunk token budget per engine step.
    # 0 = infinite budget (the legacy whole-slot path: the whole prompt
    # prefills at admit through api.prefill). Positive values split every
    # prompt into <=prefill_chunk-token chunks interleaved with decode
    # inside the same single dispatch (CHUNKABLE_FAMILIES only).
    prefill_chunk: int = 0


@dataclasses.dataclass
class _Slot:
    seq_id: int = -1
    remaining: int = 0
    request: Optional[Request] = None
    # flight-recorder bookkeeping: when this request entered the slot
    # (virtual time + engine step), so retirement can emit one decode span
    # labeled with its whole step range
    t_admit: float = 0.0
    start_step: int = 0
    # chunked prefill: non-None while the slot is still feeding its prompt
    # (cleared the step the final prompt token lands and the first
    # generated token is emitted)
    chunk: Optional[ChunkState] = None
    chunks_done: int = 0  # prefill chunks this occupancy has dispatched
    shared_pages: int = 0  # prefix pages shared at admit (span labeling)
    decode_assigned: int = 0  # decode budget granted at admit (abort books)

    @property
    def active(self) -> bool:
        return self.seq_id >= 0

    @property
    def prefilling(self) -> bool:
        return self.active and self.chunk is not None


class ServingEngine:
    def __init__(
        self,
        api: ModelAPI,
        params,
        ecfg: EngineConfig,
        seed: int = 0,
        recorder=None,
    ):
        self.api = api
        self.cfg = api.cfg
        self.ecfg = ecfg
        self.params = params
        e = ecfg
        self.pagetable = SharedKVPageTable(e.n_pages, e.page_size)
        self.placement = TieredPlacement(
            e.n_pages,
            near_capacity=max(1, int(e.near_frac * e.n_pages)),
            block_bytes=self._page_bytes(),
        )
        # pages start in the far tier until placement promotes them
        self.placement.tier[:] = 1
        self.placement.tier[: self.placement.near_capacity] = 0
        self.prefetch = PrefetchEngine(e.predictor, e.prefetch_buffer)
        self.profiler = AccessProfiler(e.n_pages, self._page_bytes(), window_len=e.placement_window)
        self.tracer = MemTracer(e.trace_window, e.trace_period)
        self.slots = [_Slot() for _ in range(e.max_batch)]
        self.cache = self._make_cache()
        # deque, not list: _admit pops the head every step, and a list's
        # pop(0) makes admission O(n^2) under backlog
        self.queue: Deque[Request] = deque()
        self.finished: List[int] = []
        self.engine_steps = 0
        # unified metrics plane: the legacy totals below are now registry
        # counters (exposed as properties for compatibility), so per-replica
        # registries merge into the fleet view bit-identically to the old
        # fleet_stats sums. A fleet wires host_rid + now_fn (via Replica);
        # standalone engines label replica=-1 and use engine steps as time.
        self.metrics = MetricsRegistry()
        self._m_tokens = self.metrics.counter("tokens_decoded")
        self._m_finished = self.metrics.counter("requests_finished")
        self._m_prefill = self.metrics.counter("prefill_tokens")
        self._m_prefill_saved = self.metrics.counter("prefill_tokens_saved")
        # delta-tracking for books owned elsewhere (placement stats, device
        # store host books): synced at drain boundaries ONLY, so the decode
        # hot path never touches the registry and the drain-cadence
        # invariant extends to every mirrored series
        self._book_seen: Dict[str, int] = {}
        self.recorder = recorder if recorder is not None else default_recorder()
        if self.recorder is not None:
            self.recorder.register(self.metrics)
        # wall-clock phases of step() (obs.FlightRecorder.phase), bound once:
        # without a recorder whose phases are on, every phase is the one
        # shared null context, so a step reads no clock and builds no
        # profiler annotation
        if self.recorder is not None and self.recorder.phases:
            self._phase = self.recorder.phase
        else:
            self._phase = lambda name, **args: PHASES_OFF
        self._m_steps = {
            kind: self.metrics.counter("engine_steps", kind=kind)
            for kind in ("decode", "chunk")
        }
        # set by the fleet: replica id for span tracks, and the shared
        # virtual clock (None -> engine steps stand in for time)
        self.host_rid = -1
        self.now_fn: Optional[Callable[[], float]] = None
        # per-tenant accounting: profiler streams are "kv.<tenant>", tier
        # hits split near/far so fleet reports can expose cross-tenant
        # interference on the shared far tier. Values are registry Counter
        # objects labeled tenant=<name> (read with .value).
        self.tenant_stats: Dict[str, Dict[str, "Counter"]] = {}
        # tenant name -> dense index into the device counter plane; stable
        # for the engine's lifetime so drained rows always map back
        self._tenant_index: Dict[str, int] = {}
        # seq id (rid) -> tenant name for every request this engine ever
        # admitted: trace-window streams ARE seq ids, so this map is what
        # lets successor training partition transitions per tenant (and is
        # exported in ReplicaProfile.stream_tenants for the fleet pool)
        self._seq_tenant: Dict[int, str] = {}
        # device-resident decode feedback: the fused decode writes the next
        # tokens here and reads them back next step without a host round-trip
        self.next_tokens = jnp.zeros((e.max_batch,), jnp.int32)
        # fleet hooks: called with (page_ids, is_write) for every accounted
        # block access — replicas attach live counters (CacheSim) here
        self.access_hooks: List[Callable] = []
        # when True, a fleet-level planner owns placement (apply_placement);
        # the local TPP epoch is suppressed so the two don't fight
        self.external_placement = False
        # degraded far-tier-only mode: the near tier is capacity-zeroed at
        # runtime (enter_degraded). Placement planning, prefetch promotion
        # and external pushes are all suspended; lookups keep flowing
        # through the same single segmented dispatch, every read a far hit.
        self.degraded = False
        # epoch fence for apply_placement: plans stamped with an epoch at
        # or below the fence predate a failover/degrade transition and are
        # rejected as stale instead of resurrecting a dead tier view
        self._placement_fence = 0
        # engine step of the last counter-plane drain — what lost_window()
        # uses to size the undrained remainder a crash leaves behind
        self._last_drain_step = 0
        # virtual-time cost of one engine step for the fleet's event
        # scheduler; replace to model batch- or far-traffic-dependent step
        # latency. Must stay constant at 1.0 for lockstep-exact replays.
        self.step_cost_fn: Optional[Callable[["ServingEngine"], float]] = None
        # host-visible fraction of this step's KV page reads that hit the
        # far tier (computed from the host tier map — no device sync).
        # step_cost_fn hooks price steps with it: far reads stall the step.
        self.last_step_far_frac = 0.0
        self._m_pf_promoted = self.metrics.counter("prefetch_promoted_pages")
        # model-dispatch books (satellite of the 1-dispatch/step budget):
        # model_dispatches counts every model executable launched — the
        # fused decode/chunk step AND any monolithic api.prefill pass the
        # whole-slot path pays per admit; prefill_dispatches counts just
        # the latter, so test_dispatch_budget can pin "chunked = exactly
        # one model dispatch per step, prefill folded in".
        self.model_dispatches = 0
        self.prefill_dispatches = 0
        # time-to-first-token: stamped at submit(), recorded the moment a
        # request's first generated token exists (admit-time under the
        # whole-slot path; the prompt-completing chunk step under chunked
        # prefill), in virtual time. The samples feed the per-tenant "ttft"
        # histogram + the pinning test.
        self._enq_vt: Dict[int, float] = {}
        self.ttft_vt_samples: List[float] = []
        # per-role (decode, prefill) x (near, far) tier hits drained from
        # the device counter plane's role accumulator
        self.role_hits = np.zeros((N_ROLES, 2), np.int64)
        # per-slot (start, end) prompt intervals of the chunk step in
        # flight, set by step() before the dispatch and consumed by
        # _account_decode + the post-step bookkeeping
        self._step_chunks: Dict[int, Tuple[int, int]] = {}
        # chunked prefill is gated per family (see CHUNKABLE_FAMILIES)
        self.chunking = e.prefill_chunk > 0 and api.family in CHUNKABLE_FAMILIES
        # one jitted decode shared by every engine on the same ModelAPI
        # (a replica fleet compiles once, not once per replica). The
        # next-token argmax is fused in and the cache buffers are donated,
        # so a steady-state step launches one executable and allocates
        # nothing new for the cache.
        if not hasattr(api, "_jit_decode"):
            serve = make_serve_step(api, vocab=self.cfg.vocab_size)

            def _decode_step(params, cache, tokens):
                nxt, cache = serve(params, cache, tokens)
                return nxt[:, 0], cache

            api._jit_decode = jax.jit(_decode_step, donate_argnums=(1,))
        self._decode = api._jit_decode
        # the continuous-batching step (make_chunk_step): ONE jitted
        # dispatch covers every prefill chunk and decode token of the step
        if not hasattr(api, "_jit_chunk_decode"):
            api._jit_chunk_decode = jax.jit(make_chunk_step(api), donate_argnums=(1,))
        self._chunk_decode = api._jit_chunk_decode
        # slot-buffer donation across join/leave churn: the batched cache
        # is threaded through jitted, donated updates — the whole-slot
        # path's prefill copy-in and the chunked path's zero-reset both
        # reuse the existing buffers instead of allocating per admit.
        if not hasattr(api, "_jit_write_slot"):

            def _write_slot_fn(dst, src, slot_idx):
                return jax.tree.map(
                    lambda d, s: _slot_put(d, s, slot_idx), dst, src
                )

            api._jit_write_slot = jax.jit(_write_slot_fn, donate_argnums=(0,))
        self._write_slot_jit = api._jit_write_slot
        if not hasattr(api, "_jit_reset_slot"):

            def _reset_slot_fn(cache, slot_idx):
                return jax.tree.map(lambda c: _slot_zero(c, slot_idx), cache)

            api._jit_reset_slot = jax.jit(_reset_slot_fn, donate_argnums=(0,))
        self._reset_slot_jit = api._jit_reset_slot
        self._rng = np.random.default_rng(seed)
        self._seed = seed
        # device-executed tiering: a device-resident near/far store whose
        # tier map mirrors placement.tier and whose fused-kernel lookups
        # produce the tier-hit counters
        self.tiered: Optional[TieredKVCache] = None
        self.tiered_max_err = 0.0  # max tiered-vs-flat read divergence seen
        self._page_wver = None  # per-page write version (fallback payloads)
        if e.device_tiering:
            self.tiered = self._make_tiered_store()
            self._page_wver = np.zeros(e.n_pages, np.int64)
            # initial fill: position the starting near set without charging
            # it to the migration books (nothing has been written yet)
            self.tiered.migrate(self.placement.near_blocks(), account=False)

    def _make_cache(self):
        """Allocate the batched slot cache. Overridable seam: the sharded
        engine places it on its mesh."""
        return self.api.init_cache(self.ecfg.max_batch, self.ecfg.max_len)

    def _make_tiered_store(self):
        """Build the device-resident tiered store. Overridable seam: the
        sharded engine returns a per-shard facade here; everything else in
        the engine talks to the store through the same interface."""
        e = self.ecfg
        return TieredKVCache(
            e.n_pages,
            self._payload_dim(),
            self.placement.near_capacity,
            identity_scales=e.tiered_identity_scales,
            counter_slots=e.max_batch,
        )

    # ------------------------------------------------------------------
    # legacy counter facade over the metrics registry (same ints, one store)

    @property
    def tokens_decoded(self) -> int:
        return self._m_tokens.value

    @property
    def prefill_tokens(self) -> int:
        return self._m_prefill.value

    @property
    def prefill_tokens_saved(self) -> int:
        return self._m_prefill_saved.value

    def now(self) -> float:
        """Virtual time if a fleet clock is attached, else engine steps."""
        return float(self.now_fn()) if self.now_fn is not None else float(self.engine_steps)

    # ------------------------------------------------------------------
    def _page_bytes(self) -> int:
        """Bytes of one logical KV page across all layers (k+v, bf16)."""
        c = self.cfg
        n_layers = getattr(c, "n_layers", 1)
        return self.ecfg.page_size * 2 * c.n_kv_heads * c.head_dim * 2 * n_layers

    # ------------------------------------------------------------------
    # device-tier payload plumbing

    def _dense_kv(self, cache) -> Optional[jnp.ndarray]:
        """The (L, B, H, S, D) k-cache when this family exposes one."""
        k = cache.get("k") if isinstance(cache, dict) else None
        return k if k is not None and getattr(k, "ndim", 0) == 5 else None

    def _payload_dim(self) -> int:
        k = self._dense_kv(self.cache)
        if k is not None:
            n_layers, _, n_heads, _, head_dim = k.shape
            return 2 * n_layers * n_heads * head_dim
        return 128  # recurrent-state families: synthetic payload rows

    def _payload_rows(self, cache, batch_idxs, positions, page_ids) -> jnp.ndarray:
        """Per-page payload rows for the device tier store (one batched
        gather for any number of (slot, position) pairs).

        For KV families the row is the real decode data: the k and v vectors
        of the page's most recently written token, flattened across layers
        and heads. Recurrent-state families (no per-position KV) fall back
        to deterministic rows keyed by (page, write-version) — the memory
        system behavior (gathers, quantization, migration) is identical, only
        the payload values are synthetic.
        """
        k = self._dense_kv(cache)
        if k is not None:
            return _kv_payload(
                k, cache["v"], jnp.asarray(batch_idxs, jnp.int32),
                jnp.asarray(positions, jnp.int32),
            )
        pids = np.asarray(page_ids, np.int64)
        return jnp.asarray(
            counter_rows(self._seed, pids, self._page_wver[pids], self.tiered.row_dim)
        )

    def _tiered_write(self, cache, batch_idxs, positions, page_ids):
        if self.tiered is None or not len(page_ids):
            return
        rows = self._payload_rows(cache, batch_idxs, positions, page_ids)
        self.tiered.write(np.asarray(page_ids, np.int64), rows)
        self._page_wver[np.asarray(page_ids, np.int64)] += 1

    def _sync_device_tiers(self):
        """Mirror placement.tier into the device store (real data movement)."""
        if self.tiered is not None:
            self.tiered.migrate(self.placement.near_blocks())

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        # stamp arrival so TTFT covers queue wait, not just slot residency
        self._enq_vt[req.rid] = self.now()
        self.queue.append(req)

    def _record_ttft(self, req: Request):
        """First generated token exists for ``req`` — close its TTFT."""
        t = self.now()
        vt = t - self._enq_vt.pop(req.rid, t)
        self.ttft_vt_samples.append(vt)
        self.metrics.histogram("ttft", tenant=req.tenant).record(vt)

    def _admit_common(self, slot_idx: int, slot: _Slot, req: Request):
        """Slot bookkeeping shared by both admission paths. Returns the
        (truncated) prompt and the pagetable share record."""
        budget = max(1, self.ecfg.max_len - 2)
        tokens = req.tokens[:budget]
        decode_len = max(1, min(req.decode_len, self.ecfg.max_len - len(tokens) - 1))
        share = self.pagetable.add_sequence(req.rid, tokens)
        self._m_prefill.inc(len(tokens))
        self._m_prefill_saved.inc(share["shared"] * self.ecfg.page_size)
        slot.seq_id = req.rid
        slot.remaining = decode_len
        slot.decode_assigned = decode_len
        slot.request = req
        slot.t_admit = self.now()
        slot.start_step = self.engine_steps
        slot.chunk = None
        slot.chunks_done = 0
        self._tenant(req.tenant)  # register the tenant counter index
        self._seq_tenant[req.rid] = req.tenant
        # the prefetch buffer is partitioned per tenant: this stream's
        # pending prefetches charge (and evict within) its tenant's share
        self.prefetch.set_stream_partition(req.rid, req.tenant)
        return tokens, share

    def _admit(self):
        """Fill freed slots from the queue — called at the top of EVERY
        step, so admission is continuous, not between-generations.

        Whole-slot path (``prefill_chunk == 0`` or a non-chunkable family):
        the prompt prefills monolithically through ``api.prefill`` — one
        extra model dispatch per admit, charged to ``prefill_dispatches``.
        Chunked path: admission only maps pages, zero-resets the slot's
        cache rows (jitted, donated — no allocation), and arms a
        ChunkState; the prompt tokens flow through the shared chunk-scan
        dispatch of subsequent steps.
        """
        for slot_idx, slot in enumerate(self.slots):
            if slot.active or not self.queue:
                continue
            req = self.queue.popleft()
            if self.chunking:
                tokens, share = self._admit_common(slot_idx, slot, req)
                self.cache = self._reset_slot_jit(
                    self.cache, jnp.int32(slot_idx)
                )
                slot.chunk = ChunkState(tokens=tokens)
                slot.shared_pages = share["shared"]
                continue
            tokens, share = self._admit_common(slot_idx, slot, req)
            # run the model prefill for this request into its slot — a
            # whole extra model dispatch outside the step's fused decode
            # (what the chunked path folds away), counted honestly
            batch = self._prefill_batch(tokens)
            logits1, cache1 = self.api.prefill(self.params, batch, max_len=self.ecfg.max_len)
            self.model_dispatches += 1
            self.prefill_dispatches += 1
            self._write_slot(slot_idx, cache1, len(tokens))
            if self.tiered is not None:
                # seed the device tier store with this sequence's page
                # payloads (each page keyed by its last prefilled token)
                pages = self.pagetable.seqs[req.rid]
                ps = self.ecfg.page_size
                positions = [
                    min((i + 1) * ps, len(tokens)) - 1 for i in range(len(pages))
                ]
                self._tiered_write(self.cache, [slot_idx] * len(pages), positions, pages)
            nxt = int(jnp.argmax(logits1[0, -1, : self.cfg.vocab_size]))
            self.next_tokens = self.next_tokens.at[slot_idx].set(nxt)
            self._record_ttft(req)
            if self.recorder is not None:
                # prefill is one batched pass at admit time: a zero-length
                # span on the request's track, sized by its args
                self.recorder.span(
                    "prefill",
                    req.rid,
                    slot.t_admit,
                    slot.t_admit,
                    tenant=req.tenant,
                    replica=self.host_rid,
                    prompt_tokens=len(tokens),
                    shared_pages=share["shared"],
                )

    def _prefill_batch(self, tokens: np.ndarray) -> dict:
        t = jnp.asarray(tokens, jnp.int32)[None, :]
        fam = self.api.family
        if fam == "vlm":
            emb = jnp.take(self.params["embed"], t, axis=0)
            pos = jnp.broadcast_to(jnp.arange(t.shape[1], dtype=jnp.int32), (3, 1, t.shape[1]))
            return {"embeds": emb, "mrope_positions": pos}
        if fam == "audio":
            frames = jnp.zeros((1, self.cfg.n_audio_frames, self.cfg.d_model), jnp.bfloat16)
            return {"tokens": t, "frames": frames}
        return {"tokens": t}

    def _write_slot(self, slot_idx: int, cache1: dict, length: int):
        """Copy a batch-1 prefill cache into slot ``slot_idx`` of the batched
        cache. Batch axis differs per leaf family (kv: axis 1; lengths:
        axis 0 — the _slot_put convention). Runs through the jitted,
        donated slot writer: the batched cache buffers are reused in place
        across join/leave churn, and because ``api.prefill`` pads to
        ``max_len`` the source shapes are fixed, so this compiles once per
        family rather than once per prompt length."""
        self.cache = self._write_slot_jit(self.cache, cache1, jnp.int32(slot_idx))

    def _chunk_plan(self):
        """Column plan for one continuous-batching step: (B, C) token ids
        plus the use-prompt / active / emit masks the chunk step consumes,
        and the per-slot ``(start, end)`` prompt intervals this dispatch
        advances. Decode slots occupy column 0 only; each prefilling slot
        takes up to ``prefill_chunk`` prompt tokens and emits (captures its
        first generated token) only in the column that consumes its final
        prompt token."""
        e = self.ecfg
        C = e.prefill_chunk
        B = e.max_batch
        tok = np.zeros((B, C), np.int32)
        use_prompt = np.zeros((B, C), bool)
        active = np.zeros((B, C), bool)
        emit = np.zeros((B, C), bool)
        spans: Dict[int, Tuple[int, int]] = {}
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            if s.prefilling:
                c = s.chunk.take(C)
                n = len(c)
                tok[i, :n] = c
                use_prompt[i, :n] = True
                active[i, :n] = True
                emit[i, n - 1] = s.chunk.pos + n >= s.chunk.total
                spans[i] = (s.chunk.pos, s.chunk.pos + n)
            else:
                active[i, 0] = True
                emit[i, 0] = True
        return tok, use_prompt, active, emit, spans

    # ------------------------------------------------------------------
    def _tenant(self, name: str) -> Dict[str, Counter]:
        if name not in self.tenant_stats:
            self.tenant_stats[name] = {
                "tokens_decoded": self.metrics.counter("tenant_tokens_decoded", tenant=name),
                "requests_finished": self.metrics.counter("tenant_requests_finished", tenant=name),
                "near_hits": self.metrics.counter("tenant_near_hits", tenant=name),
                "far_hits": self.metrics.counter("tenant_far_hits", tenant=name),
            }
        self._tenant_index.setdefault(name, len(self._tenant_index))
        return self.tenant_stats[name]

    def _sync_registry_books(self):
        """Mirror externally-owned books into the registry by delta.

        Placement stats (near/far hits, promotions, demotions, migrated
        bytes) and the device store's host books (moved rows/bytes, writes,
        dispatches, host syncs, drains) are charged by code that predates
        the registry; rather than instrument every charge site — and risk a
        hot-path cost — this syncs their *deltas* at drain boundaries. Pure
        int sums, so registry totals are bit-identical at any drain cadence.
        """

        def charge(name: str, current: int):
            seen = self._book_seen.get(name, 0)
            if current != seen:
                self.metrics.counter(name).inc(current - seen)
                self._book_seen[name] = current

        st = self.placement.stats
        charge("near_hits", st.near_hits)
        charge("far_hits", st.far_hits)
        charge("promotions", st.promotions)
        charge("demotions", st.demotions)
        charge("migrated_bytes", st.migrated_bytes)
        # prefetch books are monotone counters, so the delta-sync gives the
        # registry the same totals at any drain cadence; wasted bytes =
        # unused evictions priced at the page size the migrations pay
        pf = self.prefetch.stats
        charge("prefetch_issued_pages", pf.total_prefetched)
        charge("prefetch_used_pages", pf.used_prefetches)
        charge("prefetch_unused_evicted_pages", pf.unused_evicted)
        charge("prefetch_demand_fetches", pf.demand_fetches)
        charge(
            "prefetch_wasted_bytes", pf.unused_evicted * self.placement.block_bytes
        )
        if self.tiered is not None:
            tk = self.tiered
            charge("kv_moved_rows", tk.moved_rows)
            charge("kv_moved_bytes", tk.moved_bytes)
            charge("kv_writes", tk.writes)
            charge("kv_dispatches", tk.dispatches)
            charge("kv_host_syncs", tk.host_syncs)
            charge("kv_drains", tk.drains)

    def drain_tier_counters(self) -> Optional[dict]:
        """Drain the device counter plane and charge the host books.

        The ONE host sync of the tiered decode path, called once per
        profiler window (and at stats/export boundaries). Placement stats
        get the totals, tenant books their per-tenant-index rows — sums of
        the same per-page tier bits the per-step path charged, so the books
        are bit-identical at every drain boundary regardless of cadence.
        The metrics registry is synced here too (and ONLY here or at other
        drain boundaries), so every registry series inherits the invariant.
        """
        d = None
        self._last_drain_step = self.engine_steps
        if self.tiered is not None:
            d = self.tiered.drain_counters()
            if d["near"] or d["far"]:
                self.placement.stats.near_hits += d["near"]
                self.placement.stats.far_hits += d["far"]
                # per-role (decode/prefill) x (near/far) split: pure sums
                # of the same hits, so the drain-cadence invariant holds
                self.role_hits += np.asarray(d["role"], np.int64)
                tenant_rows = d["tenant"]
                for name, idx in self._tenant_index.items():
                    if idx < len(tenant_rows):
                        n, f = int(tenant_rows[idx][0]), int(tenant_rows[idx][1])
                        if n or f:
                            ts = self._tenant(name)
                            ts["near_hits"].inc(n)
                            ts["far_hits"].inc(f)
        self._sync_registry_books()
        return d

    def _account_decode(self, kind: str):
        """Per decode step: every active sequence touches all its KV pages
        (attention reads the whole cache) — that stream drives placement,
        prefetch, the profiler and the tracer.

        In device-tiering mode the read is EXECUTED, not modeled: ALL
        active slots' page ids go through ONE segmented tiered-gather
        dispatch, and the per-slot near/far hit counts accumulate into the
        store's device counter plane — no host sync here; the engine
        drains the plane once per profiler window.

        Under chunked prefill a prefilling slot's walk is truncated to the
        pages whose KV content exists after this step's chunk (attention
        masks the rest), and its segment carries ROLE_PREFILL into the
        counter plane's role accumulator — the mixed prefill/decode
        dispatch stays ONE kernel pass, roles ride alongside the segment
        index exactly like tenant rows do.

        Phases: ``tier.lookup`` (the step's page walks and the segmented
        lookup's host side), then ``engine.account`` (the per-slot books)."""
        with self._phase("tier.lookup", kind=kind):
            segs, segmented = self._lookup_walks()
        if segs:
            with self._phase("engine.account", kind=kind):
                self._account_slots(segs, segmented)

    def _lookup_walks(self):
        """Every active slot's page walk, and the step's one segmented
        tiered-gather dispatch over them. Returns the walks and whether the
        lookup was segmented."""
        segs = []
        for slot_idx, slot in enumerate(self.slots):
            if not slot.active:
                continue
            pages_all = self.pagetable.seqs[slot.seq_id]
            role = ROLE_DECODE
            if slot.prefilling and slot_idx in self._step_chunks:
                end = self._step_chunks[slot_idx][1]
                n_pages = -(-end // self.ecfg.page_size)
                pages = np.array(pages_all[:n_pages], np.int64)
                role = ROLE_PREFILL
            else:
                pages = np.array(pages_all, np.int64)
            if pages.size:
                segs.append((slot_idx, slot, pages, role))
        if not segs:
            return segs, False
        segmented = self.tiered is not None and self.ecfg.segmented_lookup
        if segmented:
            ids = np.concatenate([p for _, _, p, _ in segs])
            seg_of = np.repeat(
                np.arange(len(segs), dtype=np.int32),
                [p.size for _, _, p, _ in segs],
            )
            rows = self.tiered.lookup_segments(
                ids,
                seg_of,
                self.ecfg.max_batch + 1,  # last segment absorbs the padding
                slot_idx=[i for i, _, _, _ in segs],
                tenant_idx=[
                    self._tenant_index[s.request.tenant] for _, s, _, _ in segs
                ],
                role_idx=[r for _, _, _, r in segs],
            )
            if self.ecfg.tiered_verify:
                err = float(jnp.max(jnp.abs(rows - self.tiered.lookup_flat(ids))))
                self.tiered_max_err = max(self.tiered_max_err, err)
        return segs, segmented

    def _account_slots(self, segs, segmented: bool):
        """The per-slot books of one step's page walks: prefetcher,
        profiler, tracer, access hooks (and, off the segmented path, the
        per-slot lookups and tier books)."""
        far_total = n_total = 0
        for slot_idx, slot, pages, _role in segs:
            far = self.placement.tier[pages] == 1
            far_total += int(far.sum())
            n_total += pages.size
            if segmented:
                pass  # hits live in the device plane until the window drain
            else:
                if self.tiered is not None:
                    rows, near_n, far_n = self.tiered.lookup(pages)
                    self.placement.stats.near_hits += near_n
                    self.placement.stats.far_hits += far_n
                    if self.ecfg.tiered_verify:
                        err = float(
                            jnp.max(jnp.abs(rows - self.tiered.lookup_flat(pages)))
                        )
                        self.tiered_max_err = max(self.tiered_max_err, err)
                else:
                    self.placement.access(pages)
                    near_n = int((~far).sum())
                    far_n = int(far.sum())
                ts = self._tenant(slot.request.tenant)
                ts["near_hits"].inc(near_n)
                ts["far_hits"].inc(far_n)
            # stream = the sequence id: each request's page walk is its own
            # stream, so the predictor never learns cross-slot transitions
            self.prefetch.access_many(pages, far, stream=slot.seq_id)
            self.profiler.record("kv", pages)
            self.tracer.record(pages, is_write=False, stream=slot.seq_id)
            self.profiler.record(f"kv.{slot.request.tenant}", pages)
            for hook in self.access_hooks:
                hook(pages, False)
        self.last_step_far_frac = far_total / n_total if n_total else 0.0

    def _finish_chunk(self, slot_idx: int, slot: _Slot):
        """Post-dispatch bookkeeping for one prefilling slot: advance the
        chunk cursor, name the prompt pages this chunk completed for the
        tiered write path (each page keyed by its last prefilled token,
        exactly as the whole-slot admit seeds them; ``step`` writes them
        after the retire loop), and — when the final prompt token just
        landed — close TTFT: the emit column captured the request's first
        generated token into next_tokens. Returns the write as
        ``(positions, pages)``, empty without a tiered store."""
        start, end = self._step_chunks[slot_idx]
        slot.chunk.pos = end
        slot.chunks_done += 1
        w_pages: List[int] = []
        w_pos: List[int] = []
        if self.tiered is not None:
            pages = self.pagetable.seqs[slot.seq_id]
            ps = self.ecfg.page_size
            total = slot.chunk.total
            for i, pid in enumerate(pages):
                endpos = min((i + 1) * ps, total)
                if start < endpos <= end:
                    w_pages.append(pid)
                    w_pos.append(endpos - 1)
        t = self.now()
        if self.recorder is not None:
            self.recorder.span(
                "prefill_chunk",
                slot.seq_id,
                t,
                t,
                tenant=slot.request.tenant,
                replica=self.host_rid,
                tokens=end - start,
                chunk=slot.chunks_done,
            )
        if slot.chunk.done:
            prompt_tokens = slot.chunk.total
            slot.chunk = None
            self._record_ttft(slot.request)
            if self.recorder is not None:
                self.recorder.span(
                    "prefill",
                    slot.seq_id,
                    slot.t_admit,
                    t,
                    tenant=slot.request.tenant,
                    replica=self.host_rid,
                    prompt_tokens=prompt_tokens,
                    chunks=slot.chunks_done,
                    shared_pages=slot.shared_pages,
                )
        return w_pos, w_pages

    def step(self) -> int:
        """One engine iteration: admit -> decode -> account -> retire.

        Continuous batching: ``_admit`` runs at the top of EVERY step, so
        freed slots refill immediately. When any slot is mid-prefill the
        step dispatches the chunk step — prefill chunks and decode tokens
        share ONE jitted executable (and one segmented tiered-gather pass
        in ``_account_decode``); steady-state decode-only steps take the
        plain fused (B, 1) decode. Either way: one model dispatch, zero
        mandatory host syncs.

        With phases on (``obs.FlightRecorder(phases=True)``) the step is
        one ``engine.step`` phase, its ``kind`` (``decode`` or ``chunk``)
        set once admission has run, holding phases that do not overlap:
        ``engine.admit``, ``engine.plan`` (chunk steps), ``engine.dispatch``,
        ``tier.lookup``, ``engine.account``, ``engine.retire``, ``tier.write``
        and, at a placement-window boundary, ``tier.drain`` and
        ``tier.placement``.

        Returns number of tokens decoded this step.
        """
        with self._phase("engine.step") as step_phase:
            with self._phase("engine.admit"):
                self._admit()
            if not any(s.active for s in self.slots):
                return 0
            kind = "chunk" if any(s.prefilling for s in self.slots) else "decode"
            step_phase.set_kind(kind)
            self._m_steps[kind].inc()
            return self._step(kind)

    def _step(self, kind: str) -> int:
        """``step`` after admission, with at least one slot active."""
        ph = self._phase
        if kind == "chunk":
            with ph("engine.plan", kind=kind):
                tok, use_prompt, act, emit, spans = self._chunk_plan()
                self._step_chunks = spans
            with ph("engine.dispatch", kind=kind):
                self.next_tokens, self.cache = self._chunk_decode(
                    self.params,
                    self.cache,
                    self.next_tokens,
                    jnp.asarray(tok),
                    jnp.asarray(use_prompt),
                    jnp.asarray(act),
                    jnp.asarray(emit),
                )
        else:
            self._step_chunks = {}
            # one fused dispatch: decode + next-token argmax, cache donated —
            # tokens and cache stay on device, nothing reads back to host
            with ph("engine.dispatch", kind=kind):
                self.next_tokens, self.cache = self._decode(
                    self.params, self.cache, self.next_tokens[:, None]
                )
        self.model_dispatches += 1
        self._account_decode(kind)
        with ph("engine.retire", kind=kind):
            decoded, writes = self._retire()
        if self.tiered is not None and writes:
            with ph("tier.write", kind=kind):
                for slots, positions, pages in writes:
                    self._tiered_write(self.cache, slots, positions, pages)
        # profiler-window boundary: the ONE host sync of the tiered path —
        # drain the device counter plane into the host books, then run the
        # TPP epoch (skipped when a fleet planner drives placement)
        if self.engine_steps % self.ecfg.placement_window == 0:
            with ph("tier.drain", kind=kind):
                self.drain_tier_counters()
            with ph("tier.placement", kind=kind):
                self._placement_epoch()
        return decoded

    def _retire(self):
        """The step's books after its dispatch: advance prefill chunks,
        append each decoded token, retire finished requests, record the
        writes, tick the profiler and tracer. Returns the tokens decoded
        and the tiered writes the step still owes."""
        decoded = 0
        # tiered writes as (slots, positions, pages), in the order the loop
        # names them: the prompt pages each prefill chunk completed, then
        # the decoded tokens' pages
        writes: List[Tuple[List[int], List[int], List[int]]] = []
        written: List[int] = []
        written_tenant: List[str] = []
        written_slot: List[int] = []
        written_pos: List[int] = []
        written_seq: List[int] = []
        for slot_idx, slot in enumerate(self.slots):
            if not slot.active:
                continue
            if slot.prefilling:
                w_pos, w_pages = self._finish_chunk(slot_idx, slot)
                if w_pages:
                    writes.append(([slot_idx] * len(w_pages), w_pos, w_pages))
                continue
            written.append(self.pagetable.append_token(slot.seq_id))
            written_tenant.append(slot.request.tenant)
            written_slot.append(slot_idx)
            written_pos.append(self.pagetable.seq_len[slot.seq_id] - 1)
            written_seq.append(slot.seq_id)
            slot.remaining -= 1
            decoded += 1
            ts = self._tenant(slot.request.tenant)
            ts["tokens_decoded"].inc()
            if slot.remaining <= 0:
                self.pagetable.free_sequence(slot.seq_id)
                self.finished.append(slot.seq_id)
                # retire the stream's predictor tail with it — the seq id
                # never recurs, and stale tails only cost memory
                self.prefetch.drop_stream(slot.seq_id)
                ts["requests_finished"].inc()
                if self.recorder is not None:
                    t1 = self.now()
                    self.recorder.span(
                        "decode",
                        slot.seq_id,
                        slot.t_admit,
                        t1,
                        tenant=slot.request.tenant,
                        replica=self.host_rid,
                        step_range=[slot.start_step, self.engine_steps],
                    )
                    self.recorder.instant(
                        "complete",
                        slot.seq_id,
                        t1,
                        tenant=slot.request.tenant,
                        replica=self.host_rid,
                    )
                self._m_finished.inc()
                slot.seq_id = -1
                slot.request = None
        if written:
            # the decoded token's KV write — gives the access stream a real
            # R:W mix (Table 6 validation compares read:write ratios)
            w = np.asarray(written, np.int64)
            # the write is executed on device too (``step`` issues it after
            # this loop): every written page's payload row lands in its
            # current tier (quantized if far), one batched scatter for the
            # whole step
            writes.append((written_slot, written_pos, written))
            self.profiler.record("kv", w, rw="w")
            by_tenant: Dict[str, List[int]] = {}
            for page, tenant in zip(written, written_tenant):
                by_tenant.setdefault(tenant, []).append(page)
            for tenant, pages in by_tenant.items():
                self.profiler.record(f"kv.{tenant}", np.asarray(pages, np.int64), rw="w")
            self.tracer.record(w, is_write=True, stream=np.asarray(written_seq, np.int64))
            for hook in self.access_hooks:
                hook(w, True)
        self._m_tokens.inc(decoded)
        self.engine_steps += 1
        self.profiler.tick()
        self.tracer.tick()
        return decoded, writes

    def _placement_epoch(self):
        """The window boundary's placement work, after the drain."""
        # degraded mode suspends placement planning and prefetch
        # promotion — there is no near capacity to plan into — but the
        # boundary drain still runs: far hits keep charging the books at
        # the same cadence, so degraded books stay exact
        if not self.external_placement and not self.degraded:
            wins = self.profiler.windows("kv")
            if wins:
                self.placement.step(wins[-1])
                self._sync_device_tiers()
        # trace-driven prefetch issue window: runs right after the
        # boundary drain, so its apply_placement-style migration sees a
        # clean counter plane and costs ZERO additional host syncs
        # (drain_counters early-returns while the plane is clean)
        if self.ecfg.prefetch_promote and not self.degraded:
            if self.prefetch.predictor == "trace":
                # local training is tenant-partitioned like the fleet
                # push: trace streams are seq ids, and _seq_tenant maps
                # them back to the tenant whose table they train
                self.prefetch.load_successors(
                    train_tenant_successors(
                        self.tracer.windows[-32:], self._seq_tenant
                    ),
                    merge=True,
                )
            self._prefetch_window()

    def _prefetch_window(self) -> int:
        """Chase each predicted page chain and promote the predicted FAR
        pages into the near tier ahead of the decode steps that will read
        them (the paper's trace-driven prefetcher, acting).

        Candidates come from two predictions the placement counters cannot
        make: (a) each active walk's chain links and tail successors, and
        (b) the chains of QUEUED requests — a queued request's first full
        prefix page names its template via the pagetable chunk-hash, and
        the trained successor table chases the rest of the chain before a
        single count exists for it.

        Swaps are VALUE-ranked, not count-ranked: a page's value for the
        next window is the number of readers it will serve — active slots
        mapping it (pagetable ref) plus queued requests about to walk it.
        A far chain about to serve three admissions may evict a near page
        serving one; ties never churn. Among zero-value victims, pages
        deepest in the allocator's LIFO free list go first — the tail the
        allocator is about to pop would have started a fresh allocation in
        the near tier "for free".

        The swap goes through ``apply_placement``, so promotions are real
        far->near dequant copies charged to the migration books and the
        device-moved-bytes counters. Returns pages promoted.
        """
        e = self.ecfg
        preds: List[int] = []
        seen = set()
        upcoming: Dict[int, int] = {}  # page -> queued readers about to walk it
        part_of: Dict[int, str] = {}  # page -> tenant partition that predicted it
        for slot in self.slots:
            if not slot.active:
                continue
            tenant = slot.request.tenant
            pages = self.pagetable.seqs.get(slot.seq_id, [])
            if not pages:
                continue
            if slot.prefilling:
                # chunked prefill: the remaining chunk steps will read the
                # not-yet-prefilled tail of the mapped chain — count those
                # pages as upcoming readers so mid-prefill promotion is
                # amortized over the chunks instead of waiting for counts
                done = slot.chunk.pos // e.page_size
                for p in pages[done:]:
                    upcoming[p] = upcoming.get(p, 0) + 1
                    if p not in seen:
                        seen.add(p)
                        preds.append(p)
                        part_of[p] = tenant
            # the decode walk re-reads the WHOLE chain next step: chase one
            # predicted hop from every mapped page (promotes the far links
            # of a newly hot template chain the moment its head is seen),
            # then ``lookahead`` hops past the tail (the pages about to be
            # allocated and written)
            for src in pages:
                for p in self.prefetch.predict_chain(int(src), stream=slot.seq_id, lookahead=1):
                    if 0 <= p < e.n_pages and p not in seen:
                        seen.add(p)
                        preds.append(p)
                        part_of[p] = tenant
            for p in self.prefetch.predict_chain(
                int(pages[-1]), stream=slot.seq_id, lookahead=e.prefetch_lookahead
            ):
                if 0 <= p < e.n_pages and p not in seen:
                    seen.add(p)
                    preds.append(p)
                    part_of[p] = tenant
        ps = e.page_size
        for req in list(self.queue)[: e.max_batch]:
            if len(req.tokens) < ps:
                continue
            pid = self.pagetable.chains.get(
                self.pagetable._chain(0, req.tokens[:ps])
            )
            if pid is None or self.pagetable.pages[pid].ref <= 0:
                continue
            # chase the WHOLE template chain from the successor table, not
            # just prefetch_lookahead hops: a queued request's first full
            # prefix page names its template, and under chunked prefill the
            # promotion cost is amortized over the prefill chunk steps that
            # will read the chain page by page
            chain = [int(pid)] + self.prefetch.predict_chain(
                int(pid),
                stream=-1,
                lookahead=max(e.prefetch_lookahead, e.max_len // e.page_size),
                # a queued request has no live stream yet, but its tenant is
                # known: chase THAT tenant's table, never a neighbor's
                partition=req.tenant,
            )
            for p in chain:
                if not 0 <= p < e.n_pages:
                    continue
                upcoming[p] = upcoming.get(p, 0) + 1
                if p not in seen:
                    seen.add(p)
                    preds.append(p)
                    part_of[p] = req.tenant
        if not preds:
            return 0

        def value(p: int) -> int:
            # readers the page serves next window; pagetable refs are the
            # active mappers (retired sequences already dropped theirs)
            return self.pagetable.pages[p].ref + upcoming.get(p, 0)

        # stale successors may name pages the allocator has reclaimed —
        # promoting those wastes a migration on content about to be replaced
        cand = [p for p in preds if self.pagetable.pages[p].ref > 0]
        cand = [p for p in cand if self.placement.tier[p] == 1]
        if not cand:
            return 0
        cand.sort(key=value, reverse=True)
        near_ids = np.flatnonzero(self.placement.tier == 0)
        free_pos = {int(pid): i for i, pid in enumerate(self.pagetable.free)}
        victims = sorted(
            (int(b) for b in near_ids),
            key=lambda b: (value(b), free_pos.get(b, -1)),
        )
        promote: List[int] = []
        evict: List[int] = []
        for c, v in zip(cand, victims):
            if len(promote) >= e.prefetch_max_promote or value(c) <= value(v):
                break  # sorted both ways: no later pair can be profitable
            promote.append(c)
            evict.append(v)
        if not promote:
            return 0
        promote_a = np.asarray(promote, np.int64)
        evict_a = np.asarray(evict, np.int64)
        keep = np.setdiff1d(near_ids, evict_a, assume_unique=True)
        # demoted pages leave the buffer first (unused ones are waste) ...
        self.prefetch.evict(evict_a)
        self.apply_placement(np.concatenate([keep, promote_a]))
        # ... and promotions enter the books as prefetched-not-yet-used,
        # each charged to the tenant partition whose prediction named it
        self.prefetch.mark_prefetched(
            promote_a, partitions=[part_of.get(p, "") for p in promote]
        )
        self._m_pf_promoted.inc(len(promote))
        return len(promote)

    def run(self, gen: RequestGenerator, n_requests: int, max_steps: int = 10_000) -> dict:
        for _ in range(n_requests):
            self.submit(next(gen))
        steps = 0
        while (self.queue or any(s.active for s in self.slots)) and steps < max_steps:
            self.step()
            steps += 1
        return self.stats()

    # ------------------------------------------------------------------
    # fleet interface (fleet/replica.py wraps these)

    @property
    def load(self) -> int:
        """Backlog metric for routing: busy slots + queued requests."""
        return sum(1 for s in self.slots if s.active) + len(self.queue)

    def step_cost(self) -> float:
        """Virtual-time units one call to ``step`` costs (fleet scheduler).

        The default (1.0) makes engine steps the fleet's time unit; a
        ``step_cost_fn`` hook can price steps by live state instead.
        """
        if self.step_cost_fn is None:
            return 1.0
        cost = float(self.step_cost_fn(self))
        if cost <= 0.0:
            raise ValueError(f"step_cost_fn must return > 0, got {cost}")
        return cost

    def backlog_tokens(self, prefill_weight: float = 1.0) -> float:
        """Pending work in token-equivalents (admission's backlog estimate).

        ``prefill_weight`` discounts queued prompt tokens the same way the
        caller's SLO cost model does. Chunk-aware: a prefilling slot owes
        its REMAINING chunk tokens (weighted like queued prompt work — it
        occupies chunk columns, not admit-time passes), not the whole
        prompt, so AdmissionController.pressure and elastic scaling don't
        over-shed mid-prefill under chunked prefill.
        """
        q = sum(prefill_weight * len(r.tokens) + r.decode_len for r in self.queue)
        a = 0.0
        for s in self.slots:
            if not s.active:
                continue
            a += s.remaining
            if s.prefilling:
                a += prefill_weight * s.chunk.remaining
        return q + a

    def apply_placement(self, near_ids: np.ndarray, epoch: Optional[int] = None) -> int:
        """Push an externally-planned near-tier set (fleet autotier).

        Replaces the local TPP view wholesale; returns number of pages whose
        tier changed (the migration traffic this push costs).

        ``epoch`` is the planner's TierEpoch sequence number. A push whose
        epoch is at or below the engine's placement fence was planned from
        profiles gathered BEFORE a failover/degrade transition on this host
        — applying it would resurrect a tier view the failover invalidated
        — so it is rejected (counted, recorded, zero pages moved). Pushes
        while degraded are rejected the same way: there is no near
        capacity for the plan to land in.
        """
        # drain first: hits observed under the outgoing tier map are charged
        # before the map changes, so every epoch's books are exact
        self.drain_tier_counters()
        if epoch is not None and int(epoch) <= self._placement_fence:
            self.metrics.counter("placement_rejected", reason="stale_epoch").inc()
            if self.recorder is not None:
                self.recorder.instant(
                    "placement_rejected", -1, self.now(), replica=self.host_rid,
                    reason="stale_epoch", epoch=int(epoch), fence=self._placement_fence,
                )
            return 0
        if self.degraded:
            self.metrics.counter("placement_rejected", reason="degraded").inc()
            if self.recorder is not None:
                self.recorder.instant(
                    "placement_rejected", -1, self.now(), replica=self.host_rid,
                    reason="degraded",
                )
            return 0
        return self._apply_near_set(near_ids)

    def _apply_near_set(self, near_ids: np.ndarray) -> int:
        """Unconditional tier rewrite — the body ``apply_placement`` guards.
        ``enter_degraded`` calls this directly with the empty set (the
        demote-all transition must run even while the degraded flag is up).
        """
        # same sanitize rule as the device store, or the two tier views
        # diverge; dedup must precede the capacity cut so duplicate ids
        # neither double-count promotions nor shrink the near set
        near_ids = sanitize_near_ids(
            near_ids, self.ecfg.n_pages, self.placement.near_capacity
        )
        old = self.placement.tier.copy()
        self.placement.tier[:] = 1
        self.placement.tier[near_ids] = 0
        promoted = int((old[near_ids] == 1).sum())
        demoted = int(((old == 0) & (self.placement.tier == 1)).sum())
        st = self.placement.stats
        st.promotions += promoted
        st.demotions += demoted
        st.migrated_bytes += (promoted + demoted) * self.placement.block_bytes
        # device mode: the push is real data movement — promotions copy
        # far->near with dequantization, demotions quantize near->far
        self._sync_device_tiers()
        self._sync_registry_books()
        if self.recorder is not None and (promoted or demoted):
            t = self.now()
            self.recorder.span(
                "migrate",
                -1,
                t,
                t,
                replica=self.host_rid,
                promoted=promoted,
                demoted=demoted,
                bytes=(promoted + demoted) * self.placement.block_bytes,
            )
        return promoted + demoted

    # ------------------------------------------------------------------
    # failure machinery: degraded mode, epoch fencing, abort/strand books

    def fence_placement(self, epoch: int):
        """Raise the placement fence: plans stamped at or below ``epoch``
        predate this failover transition and will be rejected as stale."""
        self._placement_fence = max(self._placement_fence, int(epoch))

    def enter_degraded(self, fence_epoch: Optional[int] = None) -> int:
        """Drop to far-tier-only serving: the near tier is capacity-zeroed
        at runtime (host fault poisoned it or its HBM partition is gone).

        One accounting boundary: drain hits observed under the old map,
        then demote every resident near row through the real migration
        path — demote-first is what preserves the data, since rows in a
        dead near tier would otherwise be lost while the far mirror is
        stale. Placement planning, prefetch promotion and external pushes
        are suspended until ``exit_degraded``; the decode hot path is
        untouched (same single segmented dispatch, every read a far hit),
        so the 1-dispatch/0-mandatory-sync step budget survives the mode.
        Returns pages whose tier changed. Idempotent.
        """
        if self.degraded:
            return 0
        self.drain_tier_counters()
        self.degraded = True
        if self.tiered is not None:
            self.tiered.set_degraded(True)
        if fence_epoch is not None:
            self.fence_placement(fence_epoch)
        changed = self._apply_near_set(np.empty(0, np.int64))
        self.metrics.counter("degraded_entries").inc()
        if self.recorder is not None:
            self.recorder.instant(
                "degraded", -1, self.now(), replica=self.host_rid,
                demoted=changed,
            )
        return changed

    def exit_degraded(self, fence_epoch: Optional[int] = None):
        """Restore near-tier capacity. The near set stays empty until the
        next placement epoch (local TPP or a post-fence fleet push) refills
        it — recovery is a planning decision, not a blind restore of the
        pre-fault set. Idempotent."""
        if not self.degraded:
            return
        self.degraded = False
        if self.tiered is not None:
            self.tiered.set_degraded(False)
        if fence_epoch is not None:
            self.fence_placement(fence_epoch)
        if self.recorder is not None:
            self.recorder.instant("restored", -1, self.now(), replica=self.host_rid)

    def stranded_requests(self) -> List[Tuple[Request, int]]:
        """Read-only view of every request this engine would strand if it
        vanished right now: queued requests plus slot residents, each with
        the decode tokens already produced for it (work a failover must
        redo). Crash paths use this — the dead host's state is never
        mutated, just inventoried."""
        out: List[Tuple[Request, int]] = [(r, 0) for r in self.queue]
        for slot in self.slots:
            if slot.active:
                done = 0 if slot.chunk is not None else slot.decode_assigned - slot.remaining
                out.append((slot.request, max(0, done)))
        return out

    def abort_all(self) -> List[Tuple[Request, int]]:
        """Abort every queued and resident request (hung-host quarantine).

        Frees pagetable mappings, predictor streams and slots so a later
        re-dispatch of the same rid — here or on another replica —
        re-prefills cleanly from the request's retained prompt. Returns
        (request, decode_tokens_discarded) pairs; tokens already decoded
        stay in the books (they were really computed and streamed), the
        discarded count is the progress the retry will redo.
        """
        out: List[Tuple[Request, int]] = []
        for req in self.queue:
            self._enq_vt.pop(req.rid, None)
            out.append((req, 0))
        self.queue.clear()
        for slot in self.slots:
            if not slot.active:
                continue
            req = slot.request
            done = 0 if slot.chunk is not None else slot.decode_assigned - slot.remaining
            self.pagetable.free_sequence(slot.seq_id)
            self.prefetch.drop_stream(slot.seq_id)
            self._enq_vt.pop(slot.seq_id, None)
            slot.seq_id = -1
            slot.request = None
            slot.chunk = None
            slot.remaining = 0
            out.append((req, max(0, done)))
        if out:
            self.metrics.counter("requests_aborted").inc(len(out))
        return out

    def lost_window(self) -> dict:
        """Quantify the undrained remainder a crash leaves behind.

        The device counter plane since the last drain boundary is the one
        book a dead host cannot report; this materializes it via the
        quarantine drain (``discard=True`` — the deltas are returned but
        never folded into the host books or charged as a sync, so they can
        never leak into the fleet merge) and sizes it in steps. Everything
        already drained — the host-visible books — survives the crash by
        construction; ``salvaged + lost_window`` is therefore invariant
        under drain cadence.
        """
        steps = self.engine_steps - self._last_drain_step
        out = {"steps_undrained": int(steps), "near": 0, "far": 0}
        if self.tiered is not None:
            d = self.tiered.drain_counters(discard=True)
            out["near"] = int(d["near"])
            out["far"] = int(d["far"])
        return out

    def live_counters(self) -> dict:
        """Ground-truth counters the fleet aggregator validates against."""
        self.drain_tier_counters()
        kv = self.profiler._stream("kv")
        return {
            "reads": kv.reads,
            "writes": kv.writes,
            "rw_ratio": self.profiler.rw_ratio("kv"),
            "near_hit_rate": self.placement.stats.hit_rate,
            "accesses": int(kv.counts.sum()),
        }

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        # finalized view: pages sitting unused in the prefetch buffer at
        # report time are wasted bandwidth the LRU never got to charge
        ps = self.prefetch.finalized_stats()
        device = None
        self.drain_tier_counters()
        steps = max(self.engine_steps, 1)
        if self.tiered is not None:
            device = {
                **self.tiered.stats(),
                "max_read_error": self.tiered_max_err,
                # the dispatch/sync budget the segmented step holds to:
                # 1 dispatch and (1/placement_window) syncs per step
                "dispatches_per_step": self.tiered.dispatches / steps,
                "host_syncs_per_step": self.tiered.host_syncs / steps,
                # role split of the same tier hits (drained from the
                # counter plane's role accumulator)
                "decode_near_hits": int(self.role_hits[ROLE_DECODE, 0]),
                "decode_far_hits": int(self.role_hits[ROLE_DECODE, 1]),
                "prefill_near_hits": int(self.role_hits[ROLE_PREFILL, 0]),
                "prefill_far_hits": int(self.role_hits[ROLE_PREFILL, 1]),
            }
        tv = self.ttft_vt_samples
        return {
            "device_tiering": device,
            "serving": {
                # honest model-dispatch books: chunked prefill holds
                # model_dispatches == engine_steps (prefill folded into
                # the step's one executable); the whole-slot path pays
                # prefill_dispatches extra launches on top
                "model_dispatches": self.model_dispatches,
                "prefill_dispatches": self.prefill_dispatches,
                "model_dispatches_per_step": self.model_dispatches / steps,
                "ttft_p50": float(np.percentile(tv, 50)) if tv else 0.0,
                "ttft_p99": float(np.percentile(tv, 99)) if tv else 0.0,
                "ttft_count": len(tv),
            },
            "tokens_decoded": self.tokens_decoded,
            "requests_finished": len(self.finished),
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "near_hit_rate": self.placement.stats.hit_rate,
            "migrations": self.placement.stats.promotions + self.placement.stats.demotions,
            "prefetch_accuracy": ps.accuracy,
            "prefetch_coverage": ps.coverage,
            "prefetch_bw_overhead": ps.bw_overhead,
            "prefetch_promoted_pages": self._m_pf_promoted.value,
            "pagetable": self.pagetable.stats(),
            "tenants": {
                t: {
                    **{k: c.value for k, c in ts.items()},
                    "near_hit_rate": ts["near_hits"].value
                    / max(ts["near_hits"].value + ts["far_hits"].value, 1),
                }
                for t, ts in self.tenant_stats.items()
            },
        }

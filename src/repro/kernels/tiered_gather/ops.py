"""Public tiered-gather ops: lane padding + the two-tier composition.

``tiered_lookup_segments`` is the serving decode path's entry point: ONE
fused kernel pass resolves a whole engine step — every active slot's page
ids concatenated, with a per-gather segment index — against the device
tier map, gathers each row from the near (bf16/f32) or far (int8 +
per-row scale) store with the dequant fused in, and accumulates a
per-segment (near, far) hit pair on device. The counters stay device
arrays: nothing here forces a host sync, which is the whole point — the
engine drains them once per profiler window.

``tiered_lookup_counted`` is the per-call variant (one segment, counters
returned as int32 scalars); ``tiered_lookup`` keeps the rows-only
signature for callers that don't consume counters.

Mixed prefill/decode steps (continuous batching) change NOTHING here: a
prefill-chunk segment is just another (slot, pages) run in the same ragged
pass. The per-segment role (decode vs prefill) lives entirely in the
counter plane — ``TieredKVCache.lookup_segments(role_idx=...)`` scatters
the same per-segment hit pairs into a role-indexed accumulator alongside
the slot/tenant rows — so the kernel signature and the 1-dispatch budget
are untouched by the prefill/decode mix.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels._interpret import resolve_interpret
from repro.kernels.tiered_gather.kernel import (
    gather_rows_kernel,
    tiered_segmented_kernel,
)

LANE = 128
# the kernels' names in the compiled program and in a device trace
SEGMENTED_KERNEL = "tiered_gather_segmented"
LOOKUP_KERNEL = "tiered_gather_lookup"


def _rows3(x, dtype):
    """An (M, D) store as the kernels' (M, 1, D_pad) row view; an empty
    tier still needs one DMA-able dummy row."""
    if x.shape[0] == 0:
        x = jnp.zeros((1, x.shape[1]), dtype)
    x = x.astype(dtype)
    pad = (-x.shape[-1]) % LANE
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x[:, None, :]


def gather_rows(src, ids, scales=None, *, interpret: Optional[bool] = None):
    """src: (M, D); ids: (N,) -> (N, D) f32 (dequantized if scales given)."""
    return _gather_rows(src, ids, scales, interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_rows(src, ids, scales, *, interpret):
    d = src.shape[1]
    ids = ids.astype(jnp.int32)
    sc = None if scales is None else scales.reshape(-1).astype(jnp.float32)[ids]
    out = gather_rows_kernel(_rows3(src, src.dtype), ids, sc, interpret=interpret)
    return out[:, 0, :d]


def tiered_lookup_counted(hot, cold_q, cold_scales, tier, slot, ids,
                          *, interpret: Optional[bool] = None):
    """Two-tier lookup: near rows from ``hot`` (bf16/f32), far rows from the
    int8 ``cold_q``+``cold_scales`` store, selected by ``tier``/``slot`` maps.

    Returns (rows (N, D) f32, near_hits int32 scalar, far_hits int32 scalar):
    the hit split is counted inside the kernel, at the access point — the
    segmented pass with every gather in one segment. On real hardware the
    two gathers run on separate streams (HBM vs host DMA); here both tiers
    are DMA'd through one fused pass and merged by the tier bit.
    """
    if ids.shape[0] == 0:
        z = jnp.zeros((), jnp.int32)
        return jnp.zeros((0, hot.shape[1]), jnp.float32), z, z
    rows, hits = _tiered_lookup_segments(
        hot, cold_q, cold_scales, tier, slot, ids,
        jnp.zeros(ids.shape, jnp.int32),
        n_segments=1, interpret=resolve_interpret(interpret),
        kernel_name=LOOKUP_KERNEL,
    )
    return rows, hits[0, 0], hits[0, 1]


def tiered_lookup_segments(hot, cold_q, cold_scales, tier, slot, ids, seg_of,
                           n_segments: int, *, interpret: Optional[bool] = None):
    """Step-wide ragged lookup: one dispatch for any number of segments.

    ``ids`` (N,) is the concatenation of every segment's page ids and
    ``seg_of`` (N,) assigns each gather to a segment in [0, n_segments).
    Returns (rows (N, D) f32, seg_hits (n_segments, 2) int32) with
    seg_hits[:, 0] the near hits and seg_hits[:, 1] the far hits counted
    inside the kernel. Both results are DEVICE arrays — no host sync —
    so a caller batching a fixed segment count sees stable shapes and the
    counters can feed a device-resident accumulator plane.
    """
    n_segments = int(n_segments)
    if ids.shape[0] == 0:
        return (
            jnp.zeros((0, hot.shape[1]), jnp.float32),
            jnp.zeros((n_segments, 2), jnp.int32),
        )
    return _tiered_lookup_segments(
        hot, cold_q, cold_scales, tier, slot, ids, seg_of,
        n_segments=n_segments, interpret=resolve_interpret(interpret),
        kernel_name=SEGMENTED_KERNEL,
    )


@functools.partial(
    jax.jit, static_argnames=("n_segments", "interpret", "kernel_name")
)
def _tiered_lookup_segments(hot, cold_q, cold_scales, tier, slot, ids, seg_of,
                            *, n_segments, interpret, kernel_name):
    d = hot.shape[1]
    ids = ids.astype(jnp.int32)
    t = tier[ids].astype(jnp.int32)
    s = slot[ids].astype(jnp.int32)
    cold_ids = jnp.where(t == 1, s, 0)
    scales = cold_scales.reshape(-1).astype(jnp.float32)
    if scales.shape[0] == 0:
        scales = jnp.ones((1,), jnp.float32)
    rows, seg_hits = tiered_segmented_kernel(
        _rows3(hot, hot.dtype),
        _rows3(cold_q, jnp.int8),
        scales[cold_ids],
        t,
        jnp.where(t == 0, s, 0),
        cold_ids,
        seg_of.astype(jnp.int32),
        n_segments,
        interpret=interpret,
        name=kernel_name,
    )
    return rows[:, 0, :d], seg_hits


def tiered_lookup(hot, cold_q, cold_scales, tier, slot, ids,
                  *, interpret: Optional[bool] = None):
    """Rows-only view of :func:`tiered_lookup_counted`."""
    return tiered_lookup_counted(
        hot, cold_q, cold_scales, tier, slot, ids, interpret=interpret
    )[0]

"""Tiered row-gather Pallas TPU kernels.

Row ids are SCALAR-PREFETCHED; the source BlockSpec's index map is
data-dependent (block i = row ids[i]), so each grid step DMAs exactly one
row HBM->VMEM — a pure-bandwidth op placed exactly where the paper
puts its hot pages: the gather stream for KV pages / embedding rows /
expert blocks is the measured "few hot pages" stream, and this kernel is
the near-tier fast path. The int8 variant fuses the far-tier dequant
(per-row scale) into the same pass so promoted-but-compressed rows cost no
extra memory round-trip.

``tiered_segmented_kernel`` is the fused serving-path kernel: all active
decode slots' page ids are concatenated into ONE id vector with a
prefetched segment index per gather; one pass selects each row from the
near (bf16/f32) or far (int8 + scale) store by a prefetched tier bit,
dequantizes far rows in-register, and accumulates a per-segment (near,
far) hit pair into an SMEM counter table (constant output block index ->
the table is carried across sequential grid steps, the standard reduction
pattern). One engine step therefore costs one kernel dispatch regardless
of slot count, and the counters never leave the device — the serving
engine drains them in profiler windows instead of syncing per slot.

Block layout (what Mosaic accepts on a TPU): the last two dims of every
block must be (8, 128)-aligned or equal to the array's own. A one-row
``(1, D)`` block of an ``(M, D)`` store is neither, so every store and
output is passed as an ``(M, 1, D)`` view and each grid step takes a
``(None, 1, D)`` block, whose trailing dims equal the view's. Per-gather
dequant scales ride in ONE whole 1-D SMEM operand indexed by the grid
step (a ``(1, 1)`` block of an ``(M, 1)`` scale column breaks the same
rule), so SMEM grows with the gathers of a dispatch, like the prefetched
ids, and not with the store. D is padded to 128 lanes by ops.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._interpret import resolve_interpret


def _row_spec(d, index_map):
    return pl.BlockSpec((None, 1, d), index_map)


_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def _gather_kernel(ids_ref, src_ref, out_ref):
    out_ref[...] = src_ref[...].astype(out_ref.dtype)


def _gather_dequant_kernel(ids_ref, scale_ref, src_ref, out_ref):
    out_ref[...] = src_ref[...].astype(jnp.float32) * scale_ref[pl.program_id(0)]


def gather_rows_kernel(src, ids, scales=None, *, interpret=None):
    """src: (M, 1, D) — D a lane multiple; ids: (N,) int32; scales: (N,)
    per-gather f32 dequant scales or None.

    Returns (N, 1, D) f32.
    """
    interpret = resolve_interpret(interpret)
    d = src.shape[2]
    n = ids.shape[0]

    def src_map(i, ids_ref):
        return (ids_ref[i], 0, 0)

    def out_map(i, ids_ref):
        return (i, 0, 0)

    if scales is None:
        kernel, in_specs, operands = _gather_kernel, [], ()
    else:
        kernel, in_specs, operands = _gather_dequant_kernel, [_SMEM_WHOLE], (scales,)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=in_specs + [_row_spec(d, src_map)],
            out_specs=_row_spec(d, out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 1, d), jnp.float32),
        interpret=interpret,
    )(ids, *operands, src)


def _tiered_seg_kernel(tier_ref, hot_ids_ref, cold_ids_ref, seg_ref, scale_ref,
                       hot_ref, cold_ref, out_ref, seghits_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        def zero(j, carry):
            seghits_ref[j, 0] = 0
            seghits_ref[j, 1] = 0
            return carry

        jax.lax.fori_loop(0, seghits_ref.shape[0], zero, 0)

    near = tier_ref[i] == 0
    hot_row = hot_ref[...].astype(jnp.float32)
    cold_row = cold_ref[...].astype(jnp.float32) * scale_ref[i]
    out_ref[...] = jnp.where(near, hot_row, cold_row)
    s = seg_ref[i]
    inc = jnp.where(near, 1, 0).astype(jnp.int32)
    seghits_ref[s, 0] += inc
    seghits_ref[s, 1] += 1 - inc


def tiered_segmented_kernel(hot, cold_q, gather_scales, tier_sel, hot_ids,
                            cold_ids, seg_of, n_segments, *, interpret=None,
                            name="tiered_gather_segmented"):
    """Ragged (segmented) two-tier gather with per-segment hit counting.

    hot: (Mh, 1, D) f32/bf16; cold_q: (Mc, 1, D) int8; gather_scales: (N,)
    f32, the far scale of each gather's cold row; tier_sel/hot_ids/cold_ids:
    (N,) int32 per-gather selectors (tier bit and the row to DMA from each
    store — masked selectors must be in-range, the unused row is discarded
    by the tier select); ``seg_of`` (N,) int32 maps each gather to a
    segment in [0, n_segments). The SMEM counter table (n_segments, 2) —
    column 0 near hits, column 1 far hits — uses a constant output block
    index, so it is carried across the sequential grid steps and
    accumulated by the same pass that DMAs the rows. Callers batching
    ragged id sets to a fixed bucket size point the padding at a
    sacrificial segment and slice it off. ``name`` is the kernel's name in
    the compiled program, and so the name its operation carries in a
    device trace.

    Returns (rows (N, 1, D) f32, seg_hits (n_segments, 2) int32).
    """
    interpret = resolve_interpret(interpret)
    d = hot.shape[2]
    n = tier_sel.shape[0]

    def hot_map(i, tier_ref, hot_ids_ref, cold_ids_ref, seg_ref):
        return (hot_ids_ref[i], 0, 0)

    def cold_map(i, tier_ref, hot_ids_ref, cold_ids_ref, seg_ref):
        return (cold_ids_ref[i], 0, 0)

    def out_map(i, tier_ref, hot_ids_ref, cold_ids_ref, seg_ref):
        return (i, 0, 0)

    def hits_map(i, tier_ref, hot_ids_ref, cold_ids_ref, seg_ref):
        return (0, 0)

    return pl.pallas_call(
        _tiered_seg_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n,),
            in_specs=[
                _SMEM_WHOLE,
                _row_spec(d, hot_map),
                _row_spec(d, cold_map),
            ],
            out_specs=[
                _row_spec(d, out_map),
                pl.BlockSpec((n_segments, 2), hits_map, memory_space=pltpu.SMEM),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((n_segments, 2), jnp.int32),
        ],
        interpret=interpret,
        name=name,
    )(tier_sel, hot_ids, cold_ids, seg_of, gather_scales, hot, cold_q)

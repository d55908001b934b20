"""Device-executed tiered KV decode: the differential harness as the oracle.

Three layers of oracle, matching how the path is built:

1. kernel vs pure-jnp ref — ``tiered_lookup_counted`` against
   ``tiered_lookup_counted_ref`` across dtypes (f32/bf16 near, int8 far),
   ragged/duplicate id sets, empty-near / all-near / all-far edge cases,
   and int8 scale round-trip error bounds, as hypothesis properties.
2. engine equivalence — a seeded ``ServingEngine.run`` with device tiering
   (identity scales: quantization error zeroed) must emit the SAME tokens
   and the SAME tier-hit counters as the host-accounted path; the
   host-side accounting is the bit-exact regression oracle for the device
   path.
3. migration properties — any ``apply_placement`` push conserves pages,
   never exceeds near capacity, accounts migrated bytes exactly, and keeps
   the device tier map in lockstep with placement; a fleet AutoTierer
   epoch drives consistent device migrations on every host.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.configs.workloads import get_profile
from repro.data.requests import RequestGenerator
from repro.fleet import build_fleet, export_all, fleet_vocab, validate_fleet
from repro.kernels.tiered_gather.ops import (
    gather_rows,
    tiered_lookup,
    tiered_lookup_counted,
    tiered_lookup_segments,
)
from repro.kernels.tiered_gather.ref import (
    gather_rows_ref,
    tiered_lookup_counted_ref,
    tiered_lookup_ref,
    tiered_lookup_segments_ref,
)
from repro.models.api import get_model
from repro.runtime.serving import EngineConfig, ServingEngine
from repro.runtime.tiered_kv import TieredKVCache

# ---------------------------------------------------------------------------
# 1. kernel vs ref (differential tests)


def _tier_setup(rng, mh, mc, d, n):
    """Random two-tier layout over a page-id space of mh+mc pages."""
    m = mh + mc
    tier = np.ones(m, np.int32)
    near_ids = rng.choice(m, size=mh, replace=False) if mh else np.empty(0, np.int64)
    tier[near_ids] = 0
    slot = np.zeros(m, np.int32)
    slot[tier == 0] = np.arange(mh)
    slot[tier == 1] = np.arange(mc)
    hot = jnp.asarray(rng.standard_normal((mh, d)), jnp.float32)
    cold_q = jnp.asarray(rng.integers(-127, 128, size=(mc, d)), jnp.int8)
    scales = jnp.asarray(np.abs(rng.standard_normal(mc)) + 0.01, jnp.float32)
    ids = jnp.asarray(rng.integers(0, m, size=n), jnp.int32)
    return hot, cold_q, scales, jnp.asarray(tier), jnp.asarray(slot), ids


def _assert_counted_matches(hot, cold_q, scales, tier, slot, ids):
    rows, near, far = tiered_lookup_counted(hot, cold_q, scales, tier, slot, ids)
    r_rows, r_near, r_far = tiered_lookup_counted_ref(hot, cold_q, scales, tier, slot, ids)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(r_rows), rtol=1e-6, atol=1e-6)
    assert int(near) == int(r_near)
    assert int(far) == int(r_far)
    assert int(near) + int(far) == int(ids.shape[0])


@given(
    st.integers(0, 12),      # near rows
    st.integers(1, 24),      # far rows
    st.integers(1, 40),      # gather width (ragged, may exceed page count)
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_counted_lookup_matches_ref_property(mh, mc, n, seed):
    rng = np.random.default_rng(seed)
    _assert_counted_matches(*_tier_setup(rng, mh, mc, 64, n))


@pytest.mark.parametrize("near_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [32, 128, 200])
def test_counted_lookup_dtypes(near_dtype, d):
    rng = np.random.default_rng(3)
    hot, cold_q, scales, tier, slot, ids = _tier_setup(rng, 8, 16, d, 30)
    _assert_counted_matches(hot.astype(near_dtype), cold_q, scales, tier, slot, ids)


def test_counted_lookup_duplicate_and_repeated_ids():
    rng = np.random.default_rng(4)
    hot, cold_q, scales, tier, slot, _ = _tier_setup(rng, 4, 4, 64, 1)
    ids = jnp.asarray([0, 0, 7, 7, 7, 3, 0], jnp.int32)
    _assert_counted_matches(hot, cold_q, scales, tier, slot, ids)


def test_counted_lookup_empty_near_tier():
    rng = np.random.default_rng(5)
    hot, cold_q, scales, tier, slot, ids = _tier_setup(rng, 0, 16, 64, 20)
    rows, near, far = tiered_lookup_counted(hot, cold_q, scales, tier, slot, ids)
    assert int(near) == 0 and int(far) == 20
    _assert_counted_matches(hot, cold_q, scales, tier, slot, ids)


def test_counted_lookup_all_near_all_far():
    rng = np.random.default_rng(6)
    m, d = 12, 64
    hot = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    cold_q = jnp.asarray(rng.integers(-127, 128, size=(m, d)), jnp.int8)
    scales = jnp.ones((m,), jnp.float32)
    ids = jnp.arange(m, dtype=jnp.int32)
    slot = jnp.arange(m, dtype=jnp.int32)
    rows, near, far = tiered_lookup_counted(
        hot, cold_q, scales, jnp.zeros(m, jnp.int32), slot, ids
    )
    assert (int(near), int(far)) == (m, 0)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(hot), rtol=1e-6)
    rows, near, far = tiered_lookup_counted(
        hot, cold_q, scales, jnp.ones(m, jnp.int32), slot, ids
    )
    assert (int(near), int(far)) == (0, m)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(cold_q, np.float32), rtol=1e-6)


def test_counted_lookup_empty_ids():
    rng = np.random.default_rng(7)
    hot, cold_q, scales, tier, slot, _ = _tier_setup(rng, 4, 4, 64, 1)
    rows, near, far = tiered_lookup_counted(
        hot, cold_q, scales, tier, slot, jnp.zeros((0,), jnp.int32)
    )
    assert rows.shape == (0, 64) and int(near) == 0 and int(far) == 0


def _assert_segmented_matches(hot, cold_q, scales, tier, slot, ids, seg_of, n_seg):
    rows, hits = tiered_lookup_segments(hot, cold_q, scales, tier, slot, ids, seg_of, n_seg)
    r_rows, r_hits = tiered_lookup_segments_ref(
        hot, cold_q, scales, tier, slot, ids, seg_of, n_seg
    )
    np.testing.assert_allclose(np.asarray(rows), np.asarray(r_rows), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(r_hits))
    # per-segment counts must sum to the single-segment counted lookup —
    # segmentation refines the counters, it never changes the totals
    _, near, far = tiered_lookup_counted(hot, cold_q, scales, tier, slot, ids)
    assert int(np.asarray(hits)[:, 0].sum()) == int(near)
    assert int(np.asarray(hits)[:, 1].sum()) == int(far)


@given(
    st.integers(0, 12),      # near rows
    st.integers(1, 24),      # far rows
    st.integers(1, 40),      # total gather width across segments
    st.integers(1, 6),       # segments actually populated
    st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_segmented_lookup_matches_ref_property(mh, mc, n, n_seg, seed):
    rng = np.random.default_rng(seed)
    hot, cold_q, scales, tier, slot, ids = _tier_setup(rng, mh, mc, 64, n)
    # unsorted segment assignment: the kernel must not assume contiguity
    seg_of = jnp.asarray(rng.integers(0, n_seg, size=n), jnp.int32)
    # n_seg + 2 leaves trailing segments empty — they must count (0, 0)
    _assert_segmented_matches(hot, cold_q, scales, tier, slot, ids, seg_of, n_seg + 2)


def test_segmented_lookup_empty_ids_and_duplicates():
    rng = np.random.default_rng(9)
    hot, cold_q, scales, tier, slot, _ = _tier_setup(rng, 4, 4, 64, 1)
    rows, hits = tiered_lookup_segments(
        hot, cold_q, scales, tier, slot,
        jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32), 3,
    )
    assert rows.shape == (0, 64)
    np.testing.assert_array_equal(np.asarray(hits), np.zeros((3, 2), np.int32))
    ids = jnp.asarray([0, 0, 7, 7, 7, 3, 0], jnp.int32)
    seg_of = jnp.asarray([0, 1, 1, 0, 2, 2, 2], jnp.int32)
    _assert_segmented_matches(hot, cold_q, scales, tier, slot, ids, seg_of, 3)


def test_rows_only_wrappers_agree():
    rng = np.random.default_rng(8)
    hot, cold_q, scales, tier, slot, ids = _tier_setup(rng, 6, 10, 96, 17)
    np.testing.assert_allclose(
        np.asarray(tiered_lookup(hot, cold_q, scales, tier, slot, ids)),
        np.asarray(tiered_lookup_ref(hot, cold_q, scales, tier, slot, ids)),
        rtol=1e-6, atol=1e-6,
    )
    ids2 = jnp.asarray([1, 5, 2], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(gather_rows(hot, ids2)), np.asarray(gather_rows_ref(hot, ids2)), rtol=1e-6
    )


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_int8_scale_round_trip_bound(seed):
    """|x - dq(q(x))| <= scale/2 per element, scale = absmax/127."""
    rng = np.random.default_rng(seed)
    store = TieredKVCache(n_pages=8, row_dim=32, near_capacity=2)
    rows = jnp.asarray(rng.standard_normal((8, 32)) * (10.0 ** rng.uniform(-2, 2)), jnp.float32)
    store.write(np.arange(8), rows)  # all pages start far -> quantized
    got, near, far = store.lookup(np.arange(8))
    assert near == 0 and far == 8
    absmax = np.abs(np.asarray(rows)).max(axis=1)
    bound = absmax / 127.0 / 2.0 + 1e-7
    err = np.abs(np.asarray(got) - np.asarray(rows)).max(axis=1)
    assert (err <= bound).all(), (err, bound)


def test_identity_scales_round_trip_is_exact():
    """Snapped rows survive write -> promote -> demote -> read bit-exactly."""
    rng = np.random.default_rng(11)
    store = TieredKVCache(n_pages=16, row_dim=32, near_capacity=4, identity_scales=True)
    rows = jnp.asarray(rng.integers(-127, 128, size=(16, 32)), jnp.float32)
    store.write(np.arange(16), rows)
    for near_set in ([0, 1, 2, 3], [3, 4, 5], [12, 13, 14, 15], []):
        store.migrate(np.asarray(near_set, np.int64))
        got, _, _ = store.lookup(np.arange(16))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(rows))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(store.lookup_flat(np.arange(16))))
        # diagnostic probe agrees and never perturbs the hit counters
        hits = (store.near_hits, store.far_hits, store.lookups)
        assert store.max_abs_error(np.arange(16)) == 0.0
        assert (store.near_hits, store.far_hits, store.lookups) == hits


def test_migrate_dedups_near_ids_before_capacity_cut():
    store = TieredKVCache(n_pages=32, row_dim=16, near_capacity=5)
    store.migrate([5, 5, 1, 2, 3, 4])
    assert store.near_count == 5
    assert set(np.flatnonzero(store.tier_host == 0)) == {5, 1, 2, 3, 4}


# ---------------------------------------------------------------------------
# 2. engine equivalence: device-tiered decode vs host-accounted decode


def _mk_engine(device, **ekw):
    cfg = get_config("smollm-360m").reduced()
    api = get_model(cfg)
    if not hasattr(_mk_engine, "_params"):
        _mk_engine._params = api.init(jax.random.PRNGKey(0))
    kw = dict(
        # near_frac 0.02 -> 5 near pages of 256: the seeded workload maps
        # more pages than that, so both tiers see real traffic
        max_batch=4, max_len=64, n_pages=256, near_frac=0.02, placement_window=4,
        device_tiering=device, tiered_identity_scales=device, tiered_verify=device,
    )
    kw.update(ekw)
    return cfg, ServingEngine(api, _mk_engine._params, EngineConfig(**kw), seed=0)


def _run_collect(eng, cfg, n_requests=6, seed=0):
    prof = dataclasses.replace(
        get_profile("Web1"), prompt_mean=24, decode_mean=8, prefix_share=0.5, n_prefixes=2
    )
    gen = RequestGenerator(prof, vocab_size=cfg.vocab_size, seed=seed)
    for _ in range(n_requests):
        eng.submit(next(gen))
    tokens, steps = [], 0
    while (eng.queue or any(s.active for s in eng.slots)) and steps < 400:
        eng.step()
        tokens.append(eng.next_tokens.copy())
        steps += 1
    return np.array(tokens)


def test_payload_rows_are_the_cache_rows():
    """A written page's payload row is its token's k then v vectors, read
    straight from the slot cache: per (slot, position) pair, in order, with
    repeats, at the first and last slot and position."""
    cfg, eng = _mk_engine(True)
    rng = np.random.default_rng(3)
    k = rng.standard_normal(eng.cache["k"].shape).astype(np.float32)
    v = rng.standard_normal(eng.cache["v"].shape).astype(np.float32)
    cache = dict(eng.cache, k=jnp.asarray(k, jnp.bfloat16), v=jnp.asarray(v, jnp.bfloat16))
    k, v = (np.asarray(cache[n].astype(jnp.float32)) for n in ("k", "v"))
    b, s = k.shape[1], k.shape[3]
    slots, positions = [0, b - 1, 2, 2], [0, s - 1, 5, 5]
    got = np.asarray(eng._payload_rows(cache, slots, positions, [0, 1, 2, 3]))
    want = np.stack([
        np.concatenate([k[:, i, :, p].reshape(-1), v[:, i, :, p].reshape(-1)])
        for i, p in zip(slots, positions)
    ])
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_device_decode_bit_identical_to_host_accounting():
    """The acceptance oracle: identity scales => same tokens, same counters."""
    cfg, host = _mk_engine(False)
    t_host = _run_collect(host, cfg)
    cfg, dev = _mk_engine(True)
    t_dev = _run_collect(dev, cfg)
    np.testing.assert_array_equal(t_host, t_dev)
    assert host.live_counters() == dev.live_counters()
    sh, sd = host.stats(), dev.stats()
    for key in (
        "tokens_decoded", "requests_finished", "near_hit_rate", "migrations",
        "prefill_tokens", "prefetch_accuracy", "prefetch_coverage", "tenants",
    ):
        assert sh[key] == sd[key], key
    # the run actually exercised both tiers and the device store agrees
    # with the fleet-facing counters
    devstats = sd["device_tiering"]
    assert devstats["far_hits"] > 0 and devstats["near_hits"] > 0
    assert devstats["near_hits"] == dev.placement.stats.near_hits
    assert devstats["far_hits"] == dev.placement.stats.far_hits
    # differential probe: tiered reads never diverged from the flat buffer
    assert devstats["max_read_error"] == 0.0


@pytest.mark.slow
def test_device_mode_quantized_counters_still_match():
    """Real (absmax) scales perturb VALUES only — the control plane (tokens
    come from the model cache, counters from the tier map) stays exact."""
    cfg, host = _mk_engine(False)
    t_host = _run_collect(host, cfg, seed=3)
    cfg, dev = _mk_engine(True, tiered_identity_scales=False, tiered_verify=True)
    t_dev = _run_collect(dev, cfg, seed=3)
    np.testing.assert_array_equal(t_host, t_dev)
    assert host.live_counters() == dev.live_counters()
    # quantized far tier: reads diverge from flat, boundedly
    assert dev.stats()["device_tiering"]["far_hits"] > 0


@pytest.mark.slow
def test_fleet_trace_validation_with_device_counters():
    """Stitched fleet-trace validation stays <=5% when every host feeds the
    aggregator from device-counted tiering."""
    fleet = build_fleet(
        3, policy="prefix-affinity", seed=0, trace_window=16, trace_period=32,
        n_pages=256, near_frac=0.10, device_tiering=True, tiered_identity_scales=True,
    )
    prof = dataclasses.replace(
        get_profile("Web1"), prompt_mean=24, decode_mean=6, prefix_share=0.9, n_prefixes=3
    )
    gen = RequestGenerator(prof, vocab_size=fleet_vocab(), seed=0)
    fleet.run(gen, n_requests=12, max_steps=600, submit_per_step=2)
    profiles = export_all(fleet.replicas)
    assert all(p.device_tiering is not None for p in profiles)
    assert sum(p.device_tiering["near_hits"] + p.device_tiering["far_hits"] for p in profiles) > 0
    res = validate_fleet(profiles)
    assert res["trace_len"] > 0
    assert res["hit_ratio_error"] <= 0.05, res
    assert abs(res["rw_ratio_error_pct"]) <= 5.0, res


# ---------------------------------------------------------------------------
# 3. migration properties


@given(st.lists(st.integers(0, 255), min_size=0, max_size=64))
@settings(max_examples=25, deadline=None)
def test_apply_placement_properties(near_ids):
    if not hasattr(test_apply_placement_properties, "_eng"):
        test_apply_placement_properties._eng = _mk_engine(True)
    cfg, eng = test_apply_placement_properties._eng
    near_ids = np.asarray(near_ids, np.int64)
    st0 = dataclasses.replace(eng.placement.stats)
    changed = eng.apply_placement(near_ids)
    stats = eng.placement.stats
    promoted = stats.promotions - st0.promotions
    demoted = stats.demotions - st0.demotions
    # pages conserved: the tier map is total, near + far == n_pages
    near_n = int((eng.placement.tier == 0).sum())
    assert near_n + int((eng.placement.tier == 1).sum()) == eng.ecfg.n_pages
    # near capacity never exceeded
    assert near_n <= eng.placement.near_capacity
    # reported migration traffic is exactly (promoted + demoted) * page_bytes
    assert changed == promoted + demoted
    assert stats.migrated_bytes - st0.migrated_bytes == changed * eng.placement.block_bytes
    # device store is in lockstep with placement
    np.testing.assert_array_equal(eng.tiered.tier_host, eng.placement.tier.astype(np.int32))
    assert eng.tiered.near_count == near_n
    # near slots are a valid, duplicate-free subset of the near buffer
    slots = eng.tiered.slot_host[eng.tiered.tier_host == 0]
    assert np.unique(slots).size == slots.size
    assert ((slots >= 0) & (slots < eng.tiered.near_capacity)).all()


def test_migrate_free_slot_bookkeeping():
    store = TieredKVCache(n_pages=32, row_dim=16, near_capacity=8)
    rng = np.random.default_rng(0)
    store.write(np.arange(32), jnp.asarray(rng.standard_normal((32, 16)), jnp.float32))
    for trial in range(20):
        near = rng.choice(32, size=rng.integers(0, 9), replace=False)
        store.migrate(near)
        used = store.slot_host[store.tier_host == 0]
        assert sorted(list(used) + store._free_near) == list(range(8))
        assert store.near_count == near.size


@pytest.mark.slow
def test_autotier_epoch_migrates_consistently_on_every_host():
    """An AutoTierer epoch over 3 replicas pushes ONE fleet plan: every
    host's placement AND device tier map converge to the same near set,
    and the epoch records the device bytes the push actually moved."""
    fleet = build_fleet(
        3, policy="round-robin", seed=1, autotier=dict(near_frac=0.10, epoch_steps=8),
        n_pages=256, near_frac=0.10, device_tiering=True, tiered_identity_scales=True,
    )
    prof = dataclasses.replace(get_profile("Web1"), prompt_mean=24, decode_mean=6)
    gen = RequestGenerator(prof, vocab_size=fleet_vocab(), seed=1)
    fleet.run(gen, n_requests=12, max_steps=600, submit_per_step=2)
    at = fleet.autotierer
    assert at.history, "no tier epoch ran"
    # an explicit extra epoch, bracketed so the device-bytes attribution is
    # exact (earlier epochs interleave with initial fills / local TPP moves)
    moved_before = sum(r.engine.tiered.moved_bytes for r in fleet.replicas)
    ep = at.step(now=10_000.0)
    assert ep is not None
    assert ep.device_moved_bytes == (
        sum(r.engine.tiered.moved_bytes for r in fleet.replicas) - moved_before
    )
    # one fleet plan: every host's placement AND device map agree
    ref_tier = fleet.replicas[0].engine.placement.tier
    for r in fleet.replicas:
        np.testing.assert_array_equal(r.engine.placement.tier, ref_tier)
        np.testing.assert_array_equal(
            r.engine.tiered.tier_host, r.engine.placement.tier.astype(np.int32)
        )
        assert r.engine.tiered.near_count <= r.engine.placement.near_capacity

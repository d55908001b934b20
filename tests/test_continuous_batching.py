"""Continuous batching + chunked prefill: the equivalence contracts.

What this file pins:

1. Chunk-budget = ∞ oracle: ``prefill_chunk=0`` IS the whole-slot engine —
   same produced tokens and bit-identical live_counters as the default
   config on the same workload (the legacy path is not a near-copy, it is
   the same code).
2. Finite-chunk token equivalence: the chunked engine produces exactly the
   whole-slot engine's token stream for every request — the prompt-
   completing chunk emits the same first token ``api.prefill``'s argmax
   would have, and every subsequent decode token matches. Pinned for the
   dense family (the block pass) and for the ssm and hybrid families (the
   column scan over their single-token decode step).
3. Chunk-boundary properties: prompt length vs chunk budget edge cases
   (L == C, L = C ± 1, L < C, L = kC, L = kC + 1) take exactly
   ceil(L / C) prefill steps, then decode to completion, on both paths.
4. Slot reuse after early completion: a request admitted into a recycled
   slot (jitted zero-reset, donated buffers) decodes the same stream as on
   a fresh engine.
5. TTFT histogram pinning: the per-tenant exponential histogram's p50/p99
   bracket np.percentile of the raw virtual-time samples within one bucket
   width (relative error <= growth - 1).
"""
import dataclasses
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.workloads import get_profile
from repro.data.requests import Request, RequestGenerator
from repro.models.api import get_model
from repro.runtime.serving import EngineConfig, ServingEngine

_DENSE = "smollm-360m"
# chunkable families without a block pass: their chunk step is the scan
_SCANNED = ("rwkv6-7b", "zamba2-1.2b")  # ssm, hybrid
# one api per arch => engines share the cached jitted steps
_APIS = {}
_PARAMS = {}


def _api(arch):
    if arch not in _APIS:
        _APIS[arch] = get_model(get_config(arch).reduced())
        _PARAMS[arch] = _APIS[arch].init(jax.random.PRNGKey(0))
    return _APIS[arch]


def _mk(arch=_DENSE, **ekw):
    api = _api(arch)
    kw = dict(
        max_batch=4, max_len=64, n_pages=256, near_frac=0.02,
        placement_window=4, device_tiering=True, tiered_identity_scales=True,
    )
    kw.update(ekw)
    return ServingEngine(api, _PARAMS[arch], EngineConfig(**kw), seed=0)


def _gen(seed=0, arch=_DENSE, **pkw):
    prof = dataclasses.replace(
        get_profile("Web1"), prompt_mean=24, decode_mean=8,
        prefix_share=0.5, n_prefixes=2, **pkw,
    )
    return RequestGenerator(prof, vocab_size=_api(arch).cfg.vocab_size, seed=seed)


def _run_streams(eng, reqs, max_steps=300):
    """Drive the engine and capture each request's produced-token stream.

    The slot -> seq map is snapshotted right after ``_admit`` (retirement
    clears seq_id before the step returns) and ``next_tokens`` is read
    after the step. Mid-prefill steps produce no token and are skipped; the
    prompt-completing chunk step contributes the request's FIRST generated
    token (under whole-slot prefill that token is overwritten inside the
    admit step, so a whole-slot stream starts at the second token).
    """
    for r in reqs:
        eng.submit(r)
    snap = {}
    orig_admit = eng._admit

    def admit_and_snapshot():
        orig_admit()
        snap.clear()
        for i, s in enumerate(eng.slots):
            if s.active:
                snap[i] = s.seq_id

    eng._admit = admit_and_snapshot
    streams = defaultdict(list)
    steps = 0
    while (eng.queue or any(s.active for s in eng.slots)) and steps < max_steps:
        eng.step()
        nt = np.asarray(eng.next_tokens)
        for i, sid in snap.items():
            s = eng.slots[i]
            if s.active and s.seq_id == sid and s.prefilling:
                continue  # mid-prefill: no token produced for this slot yet
            streams[sid].append(int(nt[i]))
        steps += 1
    assert not eng.queue and not any(s.active for s in eng.slots), "run truncated"
    return dict(streams)


def _first_token(eng, tokens):
    """The whole-slot admit argmax for ``tokens`` (the reference t1)."""
    budget = max(1, eng.ecfg.max_len - 2)
    t = tokens[:budget]
    logits1, _ = eng.api.prefill(
        eng.params, eng._prefill_batch(t), max_len=eng.ecfg.max_len
    )
    return int(jnp.argmax(logits1[0, -1, : eng.cfg.vocab_size]))


# ---------------------------------------------------------------------------
# 1. chunk budget = ∞ oracle


def test_infinite_budget_is_whole_slot_bit_exact():
    runs = []
    for ekw in ({}, {"prefill_chunk": 0}):
        eng = _mk(**ekw)
        assert not eng.chunking
        gen = _gen(seed=7)
        streams = _run_streams(eng, [next(gen) for _ in range(8)])
        runs.append((streams, eng.live_counters(), eng.stats()))
    (st_a, lc_a, s_a), (st_b, lc_b, s_b) = runs
    assert st_a == st_b
    assert lc_a == lc_b
    assert s_a["tenants"] == s_b["tenants"]
    assert s_a["serving"]["prefill_dispatches"] == 8
    assert (
        s_a["serving"]["model_dispatches"]
        == s_b["serving"]["model_dispatches"]
    )


# ---------------------------------------------------------------------------
# 2. finite-chunk token equivalence


@pytest.mark.parametrize("arch", (_DENSE,) + _SCANNED)
def test_chunked_tokens_match_whole_slot(arch):
    gen = _gen(seed=3, arch=arch)
    reqs = [next(gen) for _ in range(8)]
    mono = _run_streams(_mk(arch), [dataclasses.replace(r) for r in reqs])
    eng_c = _mk(arch, prefill_chunk=8)
    assert eng_c.chunking
    assert eng_c.api.block_decode == (arch == _DENSE)
    chunked = _run_streams(eng_c, [dataclasses.replace(r) for r in reqs])
    assert set(mono) == set(chunked)
    ref = _mk(arch)  # for the t1 reference prefill passes only
    by_rid = {r.rid: r for r in reqs}
    for rid, m in mono.items():
        c = chunked[rid]
        # chunked stream = [t1(emit), t2, ...]; whole-slot capture starts
        # at t2 (t1 is consumed inside the admit step) — see _run_streams
        assert len(c) == len(m) + 1, (rid, len(c), len(m))
        assert c[1:] == m, rid
        assert c[0] == _first_token(ref, by_rid[rid].tokens), rid
    # the chunked run paid zero monolithic prefill dispatches and exactly
    # one model executable per step
    sv = eng_c.stats()["serving"]
    assert sv["prefill_dispatches"] == 0
    assert sv["model_dispatches"] == eng_c.engine_steps


# ---------------------------------------------------------------------------
# 3. chunk-boundary properties


_LENGTHS = [1, 3, 7, 8, 9, 15, 16, 17, 24, 25]


@pytest.mark.parametrize(
    "L,arch",
    [pytest.param(L, _DENSE, id=f"L{L}") for L in _LENGTHS]
    + [pytest.param(L, _SCANNED[0], id=f"{_SCANNED[0]}-L{L}") for L in _LENGTHS],
)
def test_chunk_boundaries(L, arch):
    C = 8
    eng = _mk(arch, max_batch=2, prefill_chunk=C)
    rng = np.random.default_rng(L)
    tokens = rng.integers(0, eng.cfg.vocab_size, size=L).astype(np.int32)
    eng.submit(Request(0, tokens, 3, -1, 0.0))
    prefill_steps = 0
    steps = 0
    while (eng.queue or any(s.active for s in eng.slots)) and steps < 60:
        eng.step()
        steps += 1
        if any(s.prefilling for s in eng.slots):
            prefill_steps += 1
    assert not any(s.active for s in eng.slots)
    # the prompt-completing chunk is not counted by the post-step probe
    # (chunk is already cleared), so mid-prefill steps = ceil(L/C) - 1
    expect = -(-L // C)
    assert prefill_steps == expect - 1, (L, C, prefill_steps)
    assert steps == expect + 3, (L, C, steps)  # + decode_len
    assert eng.stats()["serving"]["prefill_dispatches"] == 0


def test_slot_reuse_after_early_completion():
    """A request admitted into a recycled slot (zero-reset, donated
    buffers) must decode exactly the stream it gets on a fresh engine."""
    rng = np.random.default_rng(11)
    vocab = _api(_DENSE).cfg.vocab_size
    early = Request(0, rng.integers(0, vocab, 10).astype(np.int32), 2, -1, 0.0)
    stayer = Request(1, rng.integers(0, vocab, 20).astype(np.int32), 12, -1, 0.0)
    late = Request(2, rng.integers(0, vocab, 12).astype(np.int32), 4, -1, 0.0)
    # batch of 2: `late` queues until `early` retires, then reuses its slot
    shared = _run_streams(_mk(max_batch=2, prefill_chunk=4),
                          [dataclasses.replace(r) for r in (early, stayer, late)])
    alone = _run_streams(_mk(max_batch=2, prefill_chunk=4),
                         [dataclasses.replace(late)])
    assert shared[late.rid] == alone[late.rid]
    assert len(shared) == 3


# ---------------------------------------------------------------------------
# 4. TTFT histogram pinning


def test_ttft_histogram_pins_percentiles():
    eng = _mk(prefill_chunk=8)
    gen = _gen(seed=9)
    reqs = [next(gen) for _ in range(12)]
    _run_streams(eng, reqs)
    samples = np.asarray(eng.ttft_vt_samples)
    assert len(samples) == len(reqs)
    assert (samples >= 0).all()
    h = eng.metrics.histogram("ttft", tenant="default")
    assert h.count == len(samples)
    ordered = np.sort(samples)
    for q in (0.50, 0.99):
        # the histogram's rank convention (rank-ceil(q*count) sample); the
        # np.percentile cross-check below uses the matching method
        rank = min(len(ordered), max(1, int(np.ceil(q * len(ordered)))))
        exact = float(ordered[rank - 1])
        assert exact <= float(np.percentile(samples, 100 * q, method="higher")) + 1e-9
        got = h.quantile(q)
        # bucket upper bound: never below the true quantile, within one
        # bucket width (growth factor) above it
        assert got >= exact - 1e-9, (q, got, exact)
        assert got <= max(exact, 1e-12) * h.growth + 1e-9, (q, got, exact)

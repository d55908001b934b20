"""Core (paper-technique) invariants: profiler, distribution, tiering,
placement, prefetch, page table, memtrace — with hypothesis property tests."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import distribution as dist
from repro.core import hw
from repro.core.memtrace import CacheSim, MemTracer, validate_trace
from repro.core.pagetable import FAR, NEAR, SharedKVPageTable
from repro.core.placement import TieredPlacement
from repro.core.prefetch import PrefetchEngine
from repro.core.profiler import AccessProfiler
from repro.core.tiering import ThroughputModel, evaluate_configs, plan


# ---------------------------------------------------------------------------
# distribution / profiler


def test_bandwidth_cdf_monotone():
    rng = np.random.default_rng(0)
    counts = np.bincount(rng.zipf(1.2, 50_000) % 1024, minlength=1024)
    xs, ys = dist.bandwidth_cdf(counts)
    assert ys[0] >= 0 and abs(ys[-1] - 1.0) < 1e-9
    assert np.all(np.diff(ys) >= -1e-12)


@given(st.floats(0.05, 0.9))
@settings(max_examples=20, deadline=None)
def test_hot_fraction_dominates_capacity(frac):
    rng = np.random.default_rng(1)
    counts = np.bincount(rng.zipf(1.3, 20_000) % 512, minlength=512)
    hf = dist.hot_fraction(counts, frac)
    # hottest X% of blocks must serve at least X% of traffic
    assert hf >= frac - 1e-6


def test_profiler_correlation_identical_streams():
    prof = AccessProfiler(n_blocks=256)
    rng = np.random.default_rng(2)
    ids = rng.zipf(1.4, 5000) % 256
    prof.record("a", ids)
    prof.record("b", ids)
    prof.record("c", rng.permutation(256)[rng.integers(0, 256, 5000)])
    assert prof.correlation("a", "b") > 0.999  # Table 2 analogue
    assert prof.correlation("a", "c") < 0.9


def test_profiler_rw_ratio():
    prof = AccessProfiler(n_blocks=64)
    prof.record("s", np.arange(64), rw="r")
    prof.record("s", np.arange(32), rw="w")
    assert abs(prof.rw_ratio("s") - 2.0) < 1e-6


# ---------------------------------------------------------------------------
# tiering (paper Table 4/5)


def test_plan_places_hottest_near():
    counts = np.array([1, 100, 5, 50, 2, 80, 3, 60], float)
    p = plan(counts, hw.TIERED)
    hot = set(p.hot_blocks.tolist())
    assert {1, 5, 7} <= hot  # top blocks by count
    assert abs(sum(p.hit_fracs) - 1.0) < 1e-9
    assert p.hit_fracs[0] >= p.hit_fracs[1]


def test_table5_reproduction_band():
    """Measured-skew streams must land Tiered in the paper's band:
    >=1.3x throughput vs Baseline and better perf/cost than both."""
    rng = np.random.default_rng(3)
    counts = np.bincount(rng.zipf(1.2, 200_000) % 4096, minlength=4096)
    res = evaluate_configs(
        counts,
        {"Baseline": hw.BASELINE, "Ideal": hw.IDEAL, "Tiered": hw.TIERED},
        ThroughputModel(),
    )
    t, i, b = (res[k]["relative_throughput"] for k in ("Tiered", "Ideal", "Baseline"))
    assert b == pytest.approx(1.0, rel=1e-6)
    assert 1.30 <= t <= 1.55 and t <= i
    assert res["Tiered"]["throughput_per_cost"] > res["Baseline"]["throughput_per_cost"]
    assert res["Tiered"]["throughput_per_cost"] > res["Ideal"]["throughput_per_cost"]


# ---------------------------------------------------------------------------
# placement (TPP analogue)


def test_placement_migrates_hot_up():
    n = 128
    pl = TieredPlacement(n_blocks=n, near_capacity=32)
    rng = np.random.default_rng(4)
    hot_ids = np.arange(16)  # blocks 0..15 are hot
    for _ in range(8):
        window = np.bincount(
            np.concatenate([np.repeat(hot_ids, 20), rng.integers(0, n, 64)]), minlength=n
        )
        pl.step(window)
    near = set(pl.near_blocks().tolist())
    assert set(hot_ids.tolist()) <= near


# ---------------------------------------------------------------------------
# prefetch (paper §6 accounting)


def test_nextline_perfect_on_sequential():
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=32, degree=2)
    far = np.ones(512, bool)
    for b in range(512):
        eng.access(b, is_far=True)
    assert eng.stats.accuracy > 0.9
    assert eng.stats.coverage > 0.9


def test_random_stream_low_coverage():
    rng = np.random.default_rng(5)
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=32, degree=2)
    for b in rng.integers(0, 4096, 2000):
        eng.access(int(b), is_far=True)
    assert eng.stats.coverage < 0.5  # paper Fig. 22: low coverage
    assert eng.stats.bw_overhead > 0.0  # and real bandwidth cost (Fig. 21)


@given(st.lists(st.integers(0, 63), min_size=1, max_size=300))
@settings(max_examples=25, deadline=None)
def test_prefetch_stats_bounded(stream):
    eng = PrefetchEngine(predictor="stride", buffer_blocks=16, degree=2)
    for b in stream:
        eng.access(b, is_far=True)
    s = eng.stats
    assert 0.0 <= s.accuracy <= 1.0
    assert 0.0 <= s.coverage <= 1.0
    assert s.bw_overhead >= 0.0


# ---------------------------------------------------------------------------
# shared KV page table (multi-ASID analogue)


def test_prefix_sharing_dedups():
    pt = SharedKVPageTable(n_pages=64, page_size=4)
    prefix = list(range(8))
    pt.add_sequence(0, prefix + [100, 101])
    st1 = pt.add_sequence(1, prefix + [200])
    assert st1["shared"] == 2  # both full prefix pages shared
    assert pt.pages[pt.seqs[0][0]].ref == 2
    pt.free_sequence(0)
    assert pt.pages[pt.seqs[1][0]].ref == 1
    pt.free_sequence(1)
    assert pt.used_pages == 0


def test_append_token_cow():
    pt = SharedKVPageTable(n_pages=64, page_size=4)
    pt.add_sequence(0, [1, 2, 3, 4, 5, 6])  # page0 full, page1 fill=2
    pt.add_sequence(1, [1, 2, 3, 4, 5, 6])  # shares page0 only (tail private)
    tail0 = pt.seqs[0][-1]
    pt.append_token(0)
    assert pt.seqs[0][-1] == tail0  # private tail appended in place
    # force sharing of a full tail then COW on append
    pt2 = SharedKVPageTable(n_pages=64, page_size=4)
    pt2.add_sequence(0, [1, 2, 3, 4])
    pt2.add_sequence(1, [1, 2, 3, 4])
    assert pt2.seqs[0][-1] == pt2.seqs[1][-1]
    pid = pt2.append_token(0)  # page full -> new page, no COW needed
    assert pid != pt2.seqs[1][-1]


@given(
    st.lists(
        st.lists(st.integers(0, 3), min_size=1, max_size=24),
        min_size=1,
        max_size=12,
    )
)
@settings(max_examples=30, deadline=None)
def test_pagetable_refcount_invariants(seqs):
    pt = SharedKVPageTable(n_pages=512, page_size=4)
    for i, toks in enumerate(seqs):
        pt.add_sequence(i, toks)
    # refcount of every used page equals the number of sequences mapping it
    from collections import Counter

    mapped = Counter()
    for pages in pt.seqs.values():
        for pid in set(pages):  # a seq maps a page at most once here
            mapped[pid] += pages.count(pid)
    for pid, pg in enumerate(pt.pages):
        assert pg.ref == mapped.get(pid, 0)
    # free everything -> pool fully recovered
    for i in range(len(seqs)):
        pt.free_sequence(i)
    assert pt.used_pages == 0
    assert len(pt.free) == 512


def test_tier_bits():
    pt = SharedKVPageTable(n_pages=8, page_size=2)
    pt.add_sequence(0, [1, 2, 3, 4])
    pid = pt.seqs[0][0]
    assert pt.tier_of([pid])[0] == NEAR
    pt.set_tier(pid, FAR)
    assert pt.tier_of([pid])[0] == FAR


# ---------------------------------------------------------------------------
# memtrace (PIN-tool analogue, Table 6)


def test_trace_stitch_and_validate():
    tracer = MemTracer(window_len=16, period=64)
    rng = np.random.default_rng(6)
    blocks = rng.zipf(1.3, 20_000) % 512
    sim_full = CacheSim(capacity_blocks=64)
    for i, b in enumerate(blocks):
        tracer.tick()
        tracer.record([int(b)], is_write=(i % 3 == 0))
        sim_full.access(int(b))
    trace = tracer.stitch()
    assert tracer.overhead_frac() < 0.5  # windowed: traces a minority of time
    live_hits = sim_full.hits / max(sim_full.hits + sim_full.misses, 1)
    res = validate_trace(trace, live_hits, live_rw_ratio=2.0, capacity_blocks=64)
    assert abs(res["hit_ratio_error"]) < 0.15  # Table 6 band (<=5% in paper)
    assert len(trace.blocks) > 0

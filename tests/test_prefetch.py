"""Prefetch engine: per-stream predictors, trace training, batch contract.

Covers the two bugfixes this PR makes to core/prefetch.py —

* cross-stream contamination: predictor state (stride/last/markov training)
  is keyed per stream, so interleaved callers never teach each other
  transitions that no single request stream ever makes;
* end-of-run accounting drift: prefetches still resident at teardown are
  charged as waste by finalized_stats()/finalize(), so accuracy is not
  inflated by run-end residency —

plus the trace-trained successor path (train_successors gates, predict_chain
chasing, fleet pooling through train_fleet_successors / TierEpoch) and a
differential oracle pinning the vectorized ``access_many`` batch contract
against a plain-Python reimplementation.
"""
import collections

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core.memtrace import TraceWindow
from repro.core.prefetch import PrefetchEngine, PrefetchStats, train_successors
from repro.fleet import aggregator
from repro.fleet.replica import ReplicaProfile


def _window(blocks, streams=None, start=0):
    b = np.asarray(blocks, np.int64)
    s = None if streams is None else np.asarray(streams, np.int64)
    return TraceWindow(start, b, np.zeros(b.size, bool), s)


# ---------------------------------------------------------------------------
# satellite 1: per-stream predictor state (the contamination regression)


def test_interleaved_strided_streams_both_predict():
    """Two strided walks interleaved through one engine, tagged by stream:
    each keeps its own stride and both get covered. The pre-fix engine
    folded them into one global stream whose apparent stride was the
    inter-stream jump, covering neither."""
    eng = PrefetchEngine(predictor="stride", buffer_blocks=256, degree=2)
    a = [100 + 2 * i for i in range(64)]   # stride 2
    b = [9000 + 3 * i for i in range(64)]  # stride 3
    for x, y in zip(a, b):
        eng.access(x, is_far=True, stream="a")
        eng.access(y, is_far=True, stream="b")
    s = eng.finalized_stats()
    assert eng._streams["a"].stride == 2
    assert eng._streams["b"].stride == 3
    # after the stride locks (2 accesses) every subsequent access on each
    # stream is covered by the previous access's prefetch
    assert s.coverage > 0.9, s
    assert s.demand_fetches <= 4, s


def test_aggregate_stream_regression_guard():
    """The same interleaved traffic pushed through ONE stream id (the old
    broken behavior) must do strictly worse than the tagged run — this is
    the regression the per-stream fix exists to prevent coming back."""

    def run(tagged: bool) -> PrefetchStats:
        eng = PrefetchEngine(predictor="stride", buffer_blocks=256, degree=2)
        for i in range(64):
            eng.access(100 + 2 * i, is_far=True, stream="a" if tagged else 0)
            eng.access(9000 + 3 * i, is_far=True, stream="b" if tagged else 0)
        return eng.finalized_stats()

    good, bad = run(tagged=True), run(tagged=False)
    assert good.coverage > bad.coverage
    assert good.demand_fetches < bad.demand_fetches


def test_markov_trains_within_stream_only():
    """Interleaving A: x->y repeated with B: p->q repeated must not create
    cross-stream edges like y->p in the shared markov table."""
    eng = PrefetchEngine(predictor="markov", buffer_blocks=64, degree=1)
    for _ in range(8):
        eng.access(10, is_far=True, stream="A")
        eng.access(70, is_far=True, stream="B")
        eng.access(11, is_far=True, stream="A")
        eng.access(71, is_far=True, stream="B")
    assert set(eng._markov[10]) == {11}
    assert set(eng._markov[70]) == {71}
    assert 70 not in eng._markov[11]  # the interleave-order edge
    assert 10 not in eng._markov[71]


def test_drop_stream_forgets_training_tail():
    eng = PrefetchEngine(predictor="stride")
    eng.access(5, is_far=False, stream=3)
    assert 3 in eng._streams
    eng.drop_stream(3)
    assert 3 not in eng._streams
    eng.drop_stream(3)  # idempotent


# ---------------------------------------------------------------------------
# satellite 2: end-of-run accounting


def test_finalized_charges_resident_unused():
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=64, degree=2)
    eng.access(10, is_far=True)  # issues 11, 12; neither consumed
    assert eng.resident_unused() == 2
    live = eng.stats
    fin = eng.finalized_stats()
    assert fin.unused_evicted == live.unused_evicted + 2
    assert fin.total_prefetched == live.total_prefetched
    # non-destructive: live books and buffer untouched, second call agrees
    assert eng.resident_unused() == 2
    assert eng.finalized_stats() == fin
    # finalized books balance: every prefetch is used or wasted
    assert fin.used_prefetches + fin.unused_evicted == fin.total_prefetched


def test_finalize_flushes_buffer():
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=64, degree=2)
    eng.access(10, is_far=True)
    s = eng.finalize()
    assert eng.resident_unused() == 0
    assert s.unused_evicted == 2
    assert s is eng.stats  # finalize mutates the live books


def test_consume_on_use_one_prefetch_covers_one_miss():
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=64, degree=1)
    eng.access(0, is_far=True)            # demand fetch; issues 1
    assert eng.access(1, is_far=True)     # covered, prefetch consumed
    eng2 = PrefetchEngine(predictor="off", buffer_blocks=64)
    eng2.mark_prefetched([7])
    assert eng2.access(7, is_far=True)
    assert not eng2.access(7, is_far=False)  # already spent
    assert eng2.stats.used_prefetches == 1


def test_evict_counts_as_waste():
    eng = PrefetchEngine(predictor="off", buffer_blocks=64)
    eng.mark_prefetched([1, 2, 3])
    assert eng.evict([2, 99]) == 1  # only pending entries count
    assert eng.stats.unused_evicted == 1
    assert eng.resident_unused() == 2


# ---------------------------------------------------------------------------
# property tests


@settings(max_examples=40)
@given(
    st.lists(st.integers(min_value=0, max_value=64), min_size=1, max_size=200),
    st.sampled_from(["nextline", "stride", "markov", "trace", "off"]),
)
def test_books_invariants(blocks, predictor):
    eng = PrefetchEngine(predictor=predictor, buffer_blocks=16, degree=2)
    eng.load_successors({i: (i + 3,) for i in range(0, 64, 2)})
    for i, b in enumerate(blocks):
        eng.access(int(b), is_far=bool(b % 2), stream=i % 3)
    live, fin = eng.stats, eng.finalized_stats()
    assert live.used_prefetches + live.unused_evicted <= live.total_prefetched
    assert fin.used_prefetches + fin.unused_evicted == fin.total_prefetched
    for s in (live, fin):
        assert 0.0 <= s.accuracy <= 1.0
        assert 0.0 <= s.coverage <= 1.0
        if s.total_prefetched + s.demand_fetches > 0:
            assert s.bw_overhead >= 0.0
    assert eng.resident_unused() <= eng.capacity


@settings(max_examples=40)
@given(st.lists(st.integers(min_value=0, max_value=40), min_size=2, max_size=120))
def test_access_many_books_match_scalar_totals(blocks):
    """Fresh (never re-read) batches through access_many keep the same
    invariants as the scalar path; totals stay balanced after finalize."""
    b = np.asarray(blocks, np.int64)
    far = (b % 3 == 0)
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=16, degree=2)
    for i in range(0, b.size, 7):
        eng.access_many(b[i : i + 7], far[i : i + 7], stream=i % 2)
    s = eng.finalize()
    assert s.used_prefetches + s.unused_evicted == s.total_prefetched


# ---------------------------------------------------------------------------
# satellite 3/4: the vectorized batch contract, pinned by a plain oracle


def _oracle_access_many(eng, blocks, far_mask, stream):
    """Plain-Python reimplementation of the documented access_many
    contract: probe the whole batch first (unique hits consume), then train
    and issue only on the suffix past the stream's previous batch."""
    b = [int(x) for x in np.asarray(blocks).reshape(-1)]
    f = list(np.broadcast_to(np.asarray(far_mask, bool).reshape(-1), (len(b),)))
    hits = [blk in eng.buffer for blk in b]
    covered = sum(hits)
    eng.stats.demand_fetches += sum(1 for h, fl in zip(hits, f) if fl and not h)
    for blk in sorted({blk for blk, h in zip(b, hits) if h}):
        eng._consume(blk)
    stt = eng._stream(stream)
    prev = stt.tail
    k = 0
    if prev is not None and prev.size and len(b) >= prev.size and list(prev) == b[: prev.size]:
        k = int(prev.size)
    stt.tail = np.asarray(b, np.int64)
    if k == len(b):
        return covered
    new = b[k:]
    if k == 0 and stt.last is None:
        srcs, dsts = new[:-1], new[1:]
    else:
        last = stt.last if k == 0 else int(prev[-1])
        srcs, dsts = [last] + new[:-1], list(new)
    for a_, b_ in zip(srcs, dsts):
        if a_ != b_:
            eng._markov[a_][b_] += 1
    if srcs:
        stt.stride = (dsts[-1] - srcs[-1]) or stt.stride
    stt.last = new[-1]
    for blk in new:
        for p in eng._predict(blk, stt):
            if p >= 0:
                eng._insert(p)
    return covered


def _observable(eng):
    return (
        dataclasses_tuple(eng.stats),
        list(eng.buffer.keys()),
        {
            sid: (s.last, s.stride, None if s.tail is None else tuple(s.tail.tolist()))
            for sid, s in eng._streams.items()
        },
        {k: dict(v) for k, v in eng._markov.items()},
    )


def dataclasses_tuple(s):
    return (s.total_prefetched, s.unused_evicted, s.used_prefetches, s.demand_fetches)


@pytest.mark.parametrize("predictor", ["nextline", "stride", "markov", "trace"])
def test_access_many_differential_oracle(predictor):
    """Randomized decode-like traffic (growing re-read walks + fresh
    batches, several streams) through the vectorized path and the oracle:
    stats, buffer contents AND order (LRU state), and per-stream training
    state must agree after every single batch."""
    rng = np.random.default_rng(42)
    table = {i: (int(rng.integers(0, 256)),) for i in range(0, 256, 3)}
    vec = PrefetchEngine(predictor=predictor, buffer_blocks=32, degree=2)
    ref = PrefetchEngine(predictor=predictor, buffer_blocks=32, degree=2)
    vec.load_successors(table)
    ref.load_successors(table)
    walks = {s: list(rng.integers(0, 256, size=4)) for s in range(3)}
    for step in range(80):
        s = int(rng.integers(0, 3))
        kind = rng.random()
        if kind < 0.6:  # decode step: re-read the walk, grown by 0-2 pages
            walks[s] += [int(x) for x in rng.integers(0, 256, size=int(rng.integers(0, 3)))]
            batch = np.asarray(walks[s], np.int64)
        elif kind < 0.8:  # fresh walk (new request admitted to the slot)
            walks[s] = [int(x) for x in rng.integers(0, 256, size=int(rng.integers(1, 8)))]
            batch = np.asarray(walks[s], np.int64)
        else:  # arbitrary batch (no prefix relation)
            batch = rng.integers(0, 256, size=int(rng.integers(1, 12))).astype(np.int64)
        far = rng.random(batch.size) < 0.5
        got = vec.access_many(batch, far, stream=s)
        want = _oracle_access_many(ref, batch, far, stream=s)
        assert got == want, (step, got, want)
        assert _observable(vec) == _observable(ref), step
    assert vec.finalized_stats() == ref.finalized_stats()


def test_access_many_prefix_skip_trains_suffix_only():
    """A decode step re-reads its whole walk: only the new page may train
    or issue, and the unchanged prefix must not inflate markov counts."""
    eng = PrefetchEngine(predictor="markov", buffer_blocks=64, degree=1)
    walk = [5, 9, 2]
    eng.access_many(np.asarray(walk), np.zeros(3, bool), stream=0)
    for nxt in (17, 23, 31):
        walk.append(nxt)
        eng.access_many(np.asarray(walk), np.zeros(len(walk), bool), stream=0)
    # each edge trained exactly once despite the walk being re-read 4x
    for a, b in zip([5, 9, 2, 17, 23], [9, 2, 17, 23, 31]):
        assert eng._markov[a][b] == 1, (a, b, eng._markov[a])
    # pure re-read: nothing changes
    before = eng.stats.total_prefetched
    eng.access_many(np.asarray(walk), np.zeros(len(walk), bool), stream=0)
    assert eng.stats.total_prefetched == before


def test_access_many_probe_all_first():
    """A prefetch issued by a batch cannot cover a later element of the
    SAME batch — coverage is decided for the whole batch up front."""
    eng = PrefetchEngine(predictor="nextline", buffer_blocks=64, degree=1)
    covered = eng.access_many(np.asarray([10, 11, 12]), np.ones(3, bool), stream=0)
    assert covered == 0  # 10 issued 11, but 11's probe already happened
    assert eng.stats.demand_fetches == 3
    # the issued prefetches cover the NEXT batch
    covered = eng.access_many(np.asarray([10, 11, 12, 13]), np.ones(4, bool), stream=0)
    assert covered > 0


# ---------------------------------------------------------------------------
# trace training: gates, per-stream extraction, chain prediction


def test_train_successors_learns_chain_exactly():
    chain = [7, 301, 12, 988, 45]
    blocks = chain * 5
    table = train_successors([_window(blocks)])
    for a, b in zip(chain, chain[1:]):
        assert table[a][0] == b
    # scattered ids: nothing nextline-like invented
    assert 8 not in table.get(7, ())


def test_train_successors_per_stream_and_no_self():
    # A walks 1->2->1->2..., B walks 50->60; interleaved in one window
    blocks = [1, 50, 2, 60, 1, 50, 2, 60, 1, 50, 2, 60]
    streams = [0, 1, 0, 1, 0, 1, 0, 1, 0, 1, 0, 1]
    table = train_successors([_window(blocks, streams)])
    assert 2 in table[1] and 50 not in table.get(1, ())
    assert 60 in table[50] and 2 not in table.get(50, ())
    # self-transitions dropped
    t2 = train_successors([_window([4, 4, 4, 4, 4])])
    assert t2 == {}


def test_train_successors_confidence_gates():
    # seen once -> below min_count
    assert train_successors([_window([1, 2])]) == {}
    # 2 sightings of 1->2 but diluted below min_frac by other successors
    blocks = [1, 2, 1, 2]
    for x in range(100, 110):
        blocks += [1, x]
    table = train_successors([_window(blocks)], min_count=2, min_frac=0.3)
    assert 1 not in table  # 2/12 of the mass < 0.3
    # raise the share -> passes
    table = train_successors([_window([1, 2] * 6 + [1, 99])], min_frac=0.3)
    assert table[1] == (2,)


def test_train_successors_windows_do_not_chain():
    # window 1 ends at 7, window 2 starts at 8 (same stream id): the edge
    # 7->8 must not appear even across many window pairs
    ws = []
    for _ in range(4):
        ws.append(_window([3, 7]))
        ws.append(_window([8, 4]))
    table = train_successors(ws)
    assert 8 not in table.get(7, ())
    assert table[3] == (7,) and table[8] == (4,)


def test_predict_chain_chases_and_cuts_cycles():
    eng = PrefetchEngine(predictor="trace", degree=1)
    eng.load_successors({1: (5,), 5: (9,), 9: (3,)})
    assert eng.predict_chain(1, lookahead=3) == [5, 9, 3]
    assert eng.predict_chain(1, lookahead=2) == [5, 9]
    eng.load_successors({1: (5,), 5: (1,)})
    assert eng.predict_chain(1, lookahead=10) == [5]  # cycle cut, terminates
    assert eng.predict_chain(777, lookahead=4) == []  # untrained block


def test_trace_predictor_has_no_fallback():
    """An empty table must issue NOTHING — the no-heuristic property that
    keeps the trace predictor's wasted bandwidth at or below baselines."""
    eng = PrefetchEngine(predictor="trace", buffer_blocks=64, degree=2)
    for b in range(50):
        eng.access(b, is_far=True)
    assert eng.stats.total_prefetched == 0
    assert eng.stats.demand_fetches == 50


def test_load_successors_merge_semantics():
    eng = PrefetchEngine(predictor="trace")
    eng.load_successors({1: (2,), 3: (4,)})
    eng.load_successors({3: (9,), 5: (6,)}, merge=True)
    assert eng._successors == {1: (2,), 3: (9,), 5: (6,)}
    eng.load_successors({7: (8,)})  # wholesale replace
    assert eng._successors == {7: (8,)}


# ---------------------------------------------------------------------------
# fleet plumbing: pooled training and epoch shipping


def _profile(rid, windows):
    return ReplicaProfile(
        rid=rid, counts=np.zeros(16, np.int64), windows=windows,
        reads=0, writes=0, live_hit_ratio=0.0, live_accesses=0,
        live_capacity=4, near_hit_rate=0.0,
    )


def test_fleet_pooling_beats_per_host_tables():
    """Each host saw a transition ONCE — below min_count locally, but the
    fleet pool crosses the gate. This is why the aggregator retrains on
    pooled windows instead of merging per-host tables."""
    w0, w1 = _window([11, 12], streams=[0, 0]), _window([11, 12], streams=[0, 0])
    assert train_successors([w0]) == {}  # one sighting: below the gate
    table = aggregator.train_fleet_successors([_profile(0, [w0]), _profile(1, [w1])])
    # fleet tables are tenant-partitioned; untagged streams train ""
    assert table[""][11] == (12,)


def test_fleet_pooling_namespaces_streams_per_host():
    """Both hosts use engine stream id 0; without the rid namespace their
    windows' streams would collide. The logical BLOCK space stays shared
    (that is the point), but no spurious same-stream edges appear."""
    p0 = _profile(0, [_window([1, 2, 1, 2], streams=[0, 0, 0, 0])])
    p1 = _profile(1, [_window([7, 8, 7, 8], streams=[0, 0, 0, 0])])
    table = aggregator.train_fleet_successors([p0, p1])[""]
    assert table[1] == (2,) and table[7] == (8,)
    assert 7 not in table.get(2, ())


def test_tier_epoch_ships_prefetch_table():
    from repro.fleet.autotier import TierEpoch

    ep = TierEpoch(
        fleet_step=0, near_ids=np.zeros(0, np.int64), near_hit_frac=0.0,
        migrated_pages=0, overlap_prev=1.0,
        prefetch_table={"web": {3: (4,)}},
    )
    assert ep.prefetch_table["web"][3] == (4,)


# ---------------------------------------------------------------------------
# tenant-partitioned prefetch: table isolation + fair-share buffer


def test_train_tenant_successors_partitions_by_stream_tenant():
    from repro.core.prefetch import train_tenant_successors

    # tenant A (stream 0) walks 1->2, tenant B (stream 1) walks 7->8; both
    # twice so each crosses the min_count gate within its own partition
    w = _window([1, 7, 2, 8, 1, 7, 2, 8], streams=[0, 1, 0, 1, 0, 1, 0, 1])
    tables = train_tenant_successors([w], {0: "A", 1: "B"})
    assert tables["A"] == {1: (2,)}
    assert tables["B"] == {7: (8,)}
    # unmapped streams train the default "" partition, and empty
    # partitions are dropped rather than shipped
    tables = train_tenant_successors([w], {0: "A"})
    assert tables["A"] == {1: (2,)}
    assert tables[""] == {7: (8,)}
    assert set(tables) == {"A", ""}


def test_trace_predictions_come_from_own_tenant_table_only():
    eng = PrefetchEngine(predictor="trace", buffer_blocks=64, degree=2)
    eng.load_successors({"A": {1: (2,)}, "B": {1: (9,)}})
    eng.set_stream_partition(10, "A")
    eng.set_stream_partition(11, "B")
    assert eng.predict_chain(1, stream=10, lookahead=1) == [2]
    assert eng.predict_chain(1, stream=11, lookahead=1) == [9]
    # a stream with no partition reads the default table — empty here
    assert eng.predict_chain(1, stream=12, lookahead=1) == []
    # explicit partition override (queued requests with no stream yet)
    assert eng.predict_chain(1, stream=-1, lookahead=1, partition="B") == [9]


def test_fair_share_eviction_protects_under_share_tenant():
    """The interference fix: tenant B holds 2 pending prefetches (under its
    fair share of a 8-entry buffer); tenant A floods 20 more. Every
    overflow eviction must land on A's own entries — B's survive until B's
    demand accesses consume them."""
    eng = PrefetchEngine(predictor="trace", buffer_blocks=8)
    eng.mark_prefetched([100, 101], partitions="B")
    eng.mark_prefetched(list(range(20)), partitions="A")
    assert len(eng.buffer) == 8
    assert 100 in eng.buffer and 101 in eng.buffer
    assert eng._part_sizes == {"A": 6, "B": 2}
    # B's entries still cover B's demand accesses
    eng.set_stream_partition(1, "B")
    assert eng.access(100, is_far=True, stream=1)
    assert eng.access(101, is_far=True, stream=1)
    assert eng.stats.used_prefetches == 2


def test_over_share_inserter_pays_for_its_own_overflow():
    """When the inserting tenant is over its fair share, IT pays — oldest
    entry first — rather than pushing the cost onto its neighbor."""
    eng = PrefetchEngine(predictor="trace", buffer_blocks=4)
    eng.mark_prefetched([50], partitions="B")
    eng.mark_prefetched([0, 1, 2], partitions="A")  # full: A=3 > 4/2, B=1
    eng.mark_prefetched([3], partitions="A")
    assert 50 in eng.buffer  # B untouched
    assert 0 not in eng.buffer  # A's oldest evicted
    assert set(eng.buffer) == {50, 1, 2, 3}
    assert eng.stats.unused_evicted == 1


def test_partition_sizes_track_consume_evict_finalize():
    eng = PrefetchEngine(predictor="trace", buffer_blocks=8)
    eng.mark_prefetched([1, 2], partitions="A")
    eng.mark_prefetched([3], partitions="B")
    eng.set_stream_partition(0, "A")
    eng.access(1, is_far=True, stream=0)  # consume
    assert eng._part_sizes == {"A": 1, "B": 1}
    eng.evict([3])  # demotion eviction
    assert eng._part_sizes == {"A": 1}
    eng.finalize()
    assert eng._part_sizes == {}
    assert eng.stats.unused_evicted == 2  # evicted 3 + resident 2

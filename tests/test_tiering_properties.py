"""Property tests for core/tiering.plan invariants (paper §5).

The invariants the fleet AutoTierer leans on:

* the near set never exceeds the near tier's planned capacity;
* the near set is exactly the top-k of the measured histogram (tie-robust:
  compared by served traffic, not by id);
* the plan is invariant under rescaling the counts — hotness is a shape,
  not a magnitude, so doubling the measurement window must not change
  placement.
"""
import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.hw import TierSpec
from repro.core.tiering import plan

SPECS = (
    TierSpec("hbm", 0.25, 800.0, 1.0, 8.0),
    TierSpec("host-dram", 0.75, 100.0, 6.0, 1.0),
)


def _counts_from(values):
    # at least two blocks so near/far is a real split
    return np.asarray(values + [1, 0], dtype=np.int64)


@given(st.lists(st.integers(0, 10_000), min_size=0, max_size=64))
@settings(max_examples=50, deadline=None)
def test_plan_capacity_never_exceeded(values):
    counts = _counts_from(values)
    p = plan(counts, SPECS)
    cap = int(np.ceil(SPECS[0].capacity_frac * counts.size))
    assert p.hot_blocks.size <= cap
    assert np.unique(p.hot_blocks).size == p.hot_blocks.size  # no dup placements
    assert ((p.hot_blocks >= 0) & (p.hot_blocks < counts.size)).all()


@given(st.lists(st.integers(0, 10_000), min_size=0, max_size=64))
@settings(max_examples=50, deadline=None)
def test_plan_near_set_is_topk(values):
    counts = _counts_from(values)
    p = plan(counts, SPECS)
    k = p.hot_blocks.size
    topk_traffic = np.sort(counts)[::-1][:k].sum()
    # ties make the exact id set ambiguous; the served traffic is not
    assert counts[p.hot_blocks].sum() == topk_traffic
    assert abs(sum(p.hit_fracs) - 1.0) < 1e-9 or counts.sum() == 0


@given(
    st.lists(st.integers(0, 10_000), min_size=0, max_size=64),
    st.integers(2, 1000),
)
@settings(max_examples=50, deadline=None)
def test_plan_stable_under_count_rescaling(values, scale):
    counts = _counts_from(values)
    p1 = plan(counts, SPECS)
    p2 = plan(counts * scale, SPECS)
    # integer rescaling preserves every pairwise comparison, so the argsort
    # (and with it the physical near set) must be bit-identical
    np.testing.assert_array_equal(p1.hot_blocks, p2.hot_blocks)
    np.testing.assert_allclose(p1.hit_fracs, p2.hit_fracs, atol=1e-12)

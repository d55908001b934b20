"""End-to-end arithmetic: rates and tails from delivery stamps.

Every token the serving loop delivers carries the host-clock time at which
it reached the host. A window is the half-open interval ``(t0, t1]``: a
token, a gap or a first token counts when it ends inside it. Each tail is
taken over all samples of the window, never from medians of parts.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation between order statistics)."""
    if len(values) == 0:
        raise ValueError("no samples")
    return float(np.percentile(np.asarray(values, np.float64), q))


def tokens_in(stamps_by_request, t0: float, t1: float) -> int:
    return sum(int(np.count_nonzero((s > t0) & (s <= t1))) for s in map(np.asarray, stamps_by_request))


def itl_gaps(stamps_by_request, t0: float, t1: float) -> np.ndarray:
    """Every gap between consecutive delivered tokens of one request that
    ends in the window, in seconds."""
    out = []
    for s in stamps_by_request:
        s = np.asarray(s, np.float64)
        if s.size < 2:
            continue
        ends = s[1:]
        keep = (ends > t0) & (ends <= t1)
        out.append((ends - s[:-1])[keep])
    return np.concatenate(out) if out else np.zeros(0)


def ttfts(first_and_due, t0: float, t1: float) -> np.ndarray:
    """Time from scheduled arrival to first delivered token, for every
    request whose first token arrives in the window, in seconds."""
    return np.asarray([f - d for f, d in first_and_due if t0 < f <= t1], np.float64)
